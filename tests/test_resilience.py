"""Fault-tolerance tests: every recovery path driven by injected faults.

Covers the resilience subsystem on CPU under tier-1: retry/backoff and
the stall watchdog (unit level), hardened checkpoint saves + the
corrupt-latest fallback, the non-finite train-step guard (skip +
bit-identity), loader sample substitution, preemption re-check after
validation, and the consecutive-skip abort. The full sequenced drill
lives in ``scripts/fault_drill.py`` (exercised by a ``slow`` test here).
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import checkpoint as ckpt_lib
from raft_tpu import resilience
from raft_tpu.config import RAFTConfig, TrainConfig
from raft_tpu.models.raft import RAFT
from raft_tpu.parallel import create_train_state, make_train_step
from raft_tpu.resilience import (FaultInjector, ResilienceStats,
                                 StallWatchdog, TrainingDiverged,
                                 retry_with_backoff, set_injector)
from raft_tpu.utils.logger import TrainLogger

H, W = 64, 96


@pytest.fixture(autouse=True)
def _reset_injector():
    """Every test starts and ends with an inert process injector."""
    set_injector(FaultInjector())
    yield
    set_injector(None)


# -- unit level: retry, watchdog, injector ------------------------------


def test_retry_with_backoff_recovers():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert retry_with_backoff(flaky, retries=3, base_delay=0.001) == "ok"
    assert len(calls) == 3


def test_retry_with_backoff_exhausts_and_preserves_error():
    def always():
        raise OSError("permanent")

    with pytest.raises(OSError, match="permanent"):
        retry_with_backoff(always, retries=2, base_delay=0.001)


def test_retry_does_not_swallow_unlisted_exceptions():
    def bug():
        raise KeyError("not transient")

    with pytest.raises(KeyError):
        retry_with_backoff(bug, retries=3, base_delay=0.001)


def test_stall_watchdog_fires_and_rearms():
    msgs = []
    wd = StallWatchdog(0.05, lambda: "pump diag", sink=msgs.append)
    wd.pet()
    time.sleep(0.2)
    assert wd.fired >= 1
    assert "pump diag" in msgs[0] and "stalled" in msgs[0]
    fired_before = wd.fired
    wd.pet()          # progress: re-arms
    time.sleep(0.2)   # stalls again: second warning
    assert wd.fired > fired_before
    wd.close()


def test_stall_watchdog_quiet_when_petted():
    msgs = []
    wd = StallWatchdog(0.3, lambda: "diag", sink=msgs.append)
    for _ in range(4):
        wd.pet()
        time.sleep(0.02)
    wd.close()
    assert msgs == []


def test_stall_watchdog_close_idempotent_and_pet_noop_after_close():
    """Double close is safe, and a late pet from a draining producer
    thread must not re-arm a timer after teardown."""
    msgs = []
    wd = StallWatchdog(0.05, lambda: "diag", sink=msgs.append)
    wd.pet()
    assert wd._timer is not None and wd._timer.daemon
    wd.close()
    wd.close()                      # idempotent
    wd.pet()                        # no-op: must not re-arm
    assert wd._timer is None
    time.sleep(0.15)
    assert wd.fired == 0 and msgs == []


def test_fault_injector_from_env(monkeypatch):
    monkeypatch.setenv("RAFT_FAULT_CKPT_SAVE_ERRORS", "2")
    monkeypatch.setenv("RAFT_FAULT_CORRUPT_SAMPLES", "3, 17")
    monkeypatch.setenv("RAFT_FAULT_NAN_STEPS", "5")
    inj = FaultInjector.from_env()
    assert inj.ckpt_save_errors == 2
    assert inj.corrupt_sample_indices == frozenset({3, 17})
    assert inj.nan_loss_steps == (5,)
    assert inj.active
    assert not FaultInjector().active


def test_fault_injector_commit_errors_and_process_targeting(monkeypatch):
    monkeypatch.setenv("RAFT_FAULT_CKPT_COMMIT_ERRORS", "2")
    monkeypatch.setenv("RAFT_FAULT_TARGET_PROCESS", "1")
    inj = FaultInjector.from_env()
    assert inj.ckpt_commit_errors == 2
    assert inj.target_process == 1
    assert inj.active

    # This test runs as process 0: faults targeted at process 1 never
    # fire here and their budget is not burned...
    inj.maybe_fail_ckpt_commit()
    inj.maybe_fail_ckpt_save()
    assert inj.ckpt_commit_errors == 2
    # ...while untargeted (or process-0-targeted) faults do fire.
    on_me = FaultInjector(ckpt_commit_errors=1,
                          target_process=jax.process_index())
    with pytest.raises(OSError, match="injected checkpoint commit"):
        on_me.maybe_fail_ckpt_commit()
    assert on_me.ckpt_commit_errors == 0
    on_me.maybe_fail_ckpt_commit()  # budget exhausted: silent


def test_fault_injector_worker_knobs_from_env(monkeypatch):
    monkeypatch.setenv("RAFT_FAULT_WORKER_KILL_NTH", "3")
    monkeypatch.setenv("RAFT_FAULT_WORKER_HEARTBEAT_STALL_S", "4.5")
    monkeypatch.setenv("RAFT_FAULT_WORKER_SOCKET_DROP", "2")
    inj = FaultInjector.from_env()
    assert inj.worker_kill_nth == 3
    assert inj.worker_heartbeat_stall_s == 4.5
    assert inj.worker_socket_drop == 2
    assert inj.active
    # Each knob flips `active` on its own.
    assert FaultInjector(worker_kill_nth=1).active
    assert FaultInjector(worker_heartbeat_stall_s=1.0).active
    assert FaultInjector(worker_socket_drop=1).active


def test_fault_injector_worker_kill_nth_matches_receive_seq():
    inj = FaultInjector(worker_kill_nth=3)
    # Deterministic by receive order: exactly the nth request fires
    # (the WorkerServer does the actual os._exit).
    assert [inj.kills_worker_request(i) for i in (1, 2, 3, 4)] == \
        [False, False, True, False]
    # Disabled and off-target injectors never fire.
    assert not FaultInjector().kills_worker_request(3)
    off = FaultInjector(worker_kill_nth=3,
                        target_process=jax.process_index() + 1)
    assert not off.kills_worker_request(3)


def test_fault_injector_heartbeat_stall_is_one_shot():
    inj = FaultInjector(worker_heartbeat_stall_s=2.5)
    assert inj.take_heartbeat_stall() == 2.5
    assert inj.take_heartbeat_stall() == 0.0   # consumed
    assert inj.worker_heartbeat_stall_s == 0.0
    # Off-target: never taken, budget intact.
    off = FaultInjector(worker_heartbeat_stall_s=2.5,
                        target_process=jax.process_index() + 1)
    assert off.take_heartbeat_stall() == 0.0
    assert off.worker_heartbeat_stall_s == 2.5


def test_fault_injector_socket_drop_burns_budget():
    inj = FaultInjector(worker_socket_drop=2)
    assert inj.maybe_drop_worker_socket() is True
    assert inj.maybe_drop_worker_socket() is True
    assert inj.maybe_drop_worker_socket() is False  # budget exhausted
    assert inj.worker_socket_drop == 0
    off = FaultInjector(worker_socket_drop=1,
                        target_process=jax.process_index() + 1)
    assert off.maybe_drop_worker_socket() is False
    assert off.worker_socket_drop == 1


def test_fault_injector_new_serving_knobs(monkeypatch):
    monkeypatch.setenv("RAFT_FAULT_WORKER_PARTITION_S", "2.5")
    monkeypatch.setenv("RAFT_FAULT_GATEWAY_STALE_POOL", "2")
    inj = FaultInjector.from_env()
    assert inj.worker_partition_s == 2.5
    assert inj.gateway_stale_pool == 2
    assert inj.active
    assert FaultInjector(worker_partition_s=1.0).active
    assert FaultInjector(gateway_stale_pool=1).active
    # Partition is one-shot (the worker holds the window itself);
    # stale-pool is a per-checkout budget.
    assert inj.take_worker_partition() == 2.5
    assert inj.take_worker_partition() == 0.0
    assert inj.maybe_stale_pool() is True
    assert inj.maybe_stale_pool() is True
    assert inj.maybe_stale_pool() is False
    off = FaultInjector(worker_partition_s=1.0, gateway_stale_pool=1,
                        target_process=jax.process_index() + 1)
    assert off.take_worker_partition() == 0.0
    assert off.maybe_stale_pool() is False


def test_fault_injector_reliability_knobs(monkeypatch):
    monkeypatch.setenv("RAFT_FAULT_WORKER_DUP_DELIVERY_NTH", "2")
    monkeypatch.setenv("RAFT_FAULT_WORKER_SDC_NTH", "3")
    inj = FaultInjector.from_env()
    assert inj.worker_dup_delivery_nth == 2
    assert inj.worker_sdc_nth == 3
    assert inj.active
    assert FaultInjector(worker_dup_delivery_nth=1).active
    assert FaultInjector(worker_sdc_nth=1).active
    # Both fire deterministically on exactly their 1-based sequence
    # number: dup-delivery by receive order, SDC by self-check order.
    assert [inj.duplicates_worker_request(i) for i in (1, 2, 3)] == \
        [False, True, False]
    assert [inj.corrupts_self_check(i) for i in (1, 2, 3, 4)] == \
        [False, False, True, False]
    # Disabled and off-target injectors never fire.
    assert not FaultInjector().duplicates_worker_request(2)
    assert not FaultInjector().corrupts_self_check(3)
    off = FaultInjector(worker_dup_delivery_nth=2, worker_sdc_nth=3,
                        target_process=jax.process_index() + 1)
    assert not off.duplicates_worker_request(2)
    assert not off.corrupts_self_check(3)


def test_fault_knob_docstring_matches_from_env():
    """Consistency lint: every RAFT_FAULT_* knob documented in the
    FaultInjector docstring is parsed by from_env, and every knob
    from_env parses is documented. A knob added on one side only is a
    silent no-op waiting to burn a drill."""
    import inspect
    import re

    pat = re.compile(r"RAFT_FAULT_[A-Z0-9_]+")
    documented = set(pat.findall(FaultInjector.__doc__ or ""))
    parsed = set(pat.findall(inspect.getsource(FaultInjector.from_env)))
    assert documented, "FaultInjector docstring lists no knobs?"
    missing_parse = documented - parsed
    missing_docs = parsed - documented
    assert not missing_parse, \
        f"documented but never parsed by from_env: {missing_parse}"
    assert not missing_docs, \
        f"parsed by from_env but undocumented: {missing_docs}"


# -- checkpoint hardening -----------------------------------------------


_STATE_CACHE = {}


def _tiny_state(seed=0, step=None):
    """Tiny RAFT train state; model init is cached per seed (it is the
    dominant cost of every checkpoint test)."""
    tcfg = TrainConfig(name="t", num_steps=4, batch_size=2,
                       image_size=(H, W), iters=2, val_freq=1000,
                       sum_freq=2)
    if seed not in _STATE_CACHE:
        mcfg = RAFTConfig(small=True, iters=2)
        model = RAFT(mcfg)
        _STATE_CACHE[seed] = (model, create_train_state(
            jax.random.PRNGKey(seed), model, tcfg, (H, W)))
    model, state = _STATE_CACHE[seed]
    if step is not None:
        state = state.replace(step=jnp.asarray(step, jnp.int32))
    return tcfg, model, state


def test_checkpoint_save_retries_injected_io_errors(tmp_path, capsys):
    _, _, state = _tiny_state(step=3)
    set_injector(FaultInjector(ckpt_save_errors=2))
    d = str(tmp_path / "ckpt")
    with ckpt_lib.RunCheckpointer(d, save_retries=3,
                                  retry_delay=0.001) as ckptr:
        ckptr.save(state)
        assert ckptr.latest_step() == 3
    assert "retrying" in capsys.readouterr().out


def test_checkpoint_save_raises_when_retries_exhausted(tmp_path):
    _, _, state = _tiny_state(step=3)
    set_injector(FaultInjector(ckpt_save_errors=99))
    with ckpt_lib.RunCheckpointer(str(tmp_path / "ckpt"), save_retries=2,
                                  retry_delay=0.001) as ckptr:
        with pytest.raises(OSError, match="injected"):
            ckptr.save(state)


def _corrupt_truncate(step_dir):
    for root, _, files in os.walk(step_dir):
        for f in files:
            open(os.path.join(root, f), "w").close()


def test_restore_falls_back_past_corrupt_latest(tmp_path):
    """Preemption mid-save: the newest step is truncated (zero-byte
    files) and the one below is missing its manifest; both are skipped
    and the newest intact step restores."""
    _, model, state = _tiny_state()
    d = str(tmp_path / "ckpt")
    with ckpt_lib.RunCheckpointer(d) as ckptr:
        for s in (3, 5, 7):
            ckptr.save(state.replace(step=jnp.asarray(s, jnp.int32)))

    # step 7: truncated files -> caught by the structural screen
    _corrupt_truncate(os.path.join(d, "7"))
    # step 5: structurally plausible but unrestorable -> caught by the
    # restore-time fallback
    os.remove(os.path.join(d, "5", "default", "manifest.ocdbt"))

    assert ckpt_lib.latest_step(d) in (3, 5)   # 7 is screened out
    _, _, fresh = _tiny_state(seed=1)
    restored = ckpt_lib.restore_checkpoint(d, fresh)
    assert int(restored.step) == 3
    l0 = jax.tree.leaves(state.params)[0]
    l1 = jax.tree.leaves(restored.params)[0]
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))


def test_restore_explicit_step_still_raises_on_corruption(tmp_path):
    _, _, state = _tiny_state()
    d = str(tmp_path / "ckpt")
    with ckpt_lib.RunCheckpointer(d) as ckptr:
        ckptr.save(state.replace(step=jnp.asarray(7, jnp.int32)))
    os.remove(os.path.join(d, "7", "default", "manifest.ocdbt"))
    _, _, fresh = _tiny_state(seed=1)
    with pytest.raises(Exception):
        ckpt_lib.restore_checkpoint(d, fresh, step=7)


# -- async saves + commit agreement -------------------------------------


class _FakeState:
    """Minimal checkpointable state (mirrors the drill's ``_TinyState``)
    — async/commit semantics don't depend on the state's size, and a
    real RAFT state would dominate the runtime of every test here."""

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


def test_async_save_gates_commit_and_restores_during_pending(tmp_path):
    """The in-flight async step is invisible to latest/restore until
    the wait_for_pending barrier commits it (satellite d)."""
    d = str(tmp_path / "ckpt")
    with ckpt_lib.RunCheckpointer(d, async_save=True) as c:
        # First save in flight, nothing committed yet: a restore during
        # the pending save returns the caller's state unchanged.
        c.save(_FakeState(1))
        assert c.pending_step == 1
        assert c.latest_step() is None
        probe = _FakeState(0)
        assert ckpt_lib.restore_checkpoint(d, probe) is probe
        c.wait_for_pending()
        assert c.pending_step is None and c.latest_step() == 1

        c.save(_FakeState(2))
        assert c.pending_step == 2
        # Both this manager and a fresh reader see only the committed
        # step while 2 is in flight.
        assert c.latest_step() == 1
        assert ckpt_lib.latest_step(d) == 1
        got = ckpt_lib.restore_checkpoint(d, _FakeState(0))
        assert int(got.step) == 1
        np.testing.assert_array_equal(np.asarray(got.params["w"]),
                                      np.arange(8, dtype=np.float32))
        c.wait_for_pending()
        assert c.latest_step() == 2
    assert ckpt_lib.latest_step(d) == 2


def test_async_save_dispatch_does_not_finalize_inline(tmp_path):
    """``save`` in async mode only dispatches: the finalize/vote/commit
    path (``_save_with_agreement``) runs at the barrier, not inline."""
    d = str(tmp_path / "ckpt")
    with ckpt_lib.RunCheckpointer(d, async_save=True) as c:
        calls = []
        orig = c._save_with_agreement
        c._save_with_agreement = \
            lambda *a, **kw: (calls.append(a[0]), orig(*a, **kw))[1]
        c.save(_FakeState(1))
        assert calls == []          # dispatch returned without finalizing
        c.wait_for_pending()
        assert calls == [1]         # the barrier did
        c.wait_for_pending()
        assert calls == [1]         # idempotent: nothing pending


def test_async_commit_failure_rolls_back_to_older_step(tmp_path):
    """A host that dies between its write and its vote (injected commit
    failure outlasting the retry budget) must not leave a torn step:
    the step dir is rolled back and restore lands on the previous
    committed step."""
    d = str(tmp_path / "ckpt")
    with ckpt_lib.RunCheckpointer(d, async_save=True, save_retries=1,
                                  retry_delay=0.001) as c:
        c.save(_FakeState(1))
        c.wait_for_pending()        # baseline commit
        set_injector(FaultInjector(ckpt_commit_errors=8))
        c.save(_FakeState(2))       # dispatch succeeds (write-side OK)
        with pytest.raises(OSError, match="injected checkpoint commit"):
            c.wait_for_pending()
        set_injector(FaultInjector())
        assert not os.path.isdir(os.path.join(d, "2"))   # rolled back
        assert c.latest_step() == 1
    got = ckpt_lib.restore_checkpoint(d, _FakeState(0))
    assert int(got.step) == 1


def test_uncommitted_step_invisible_to_fresh_reader(tmp_path):
    """Commit gating is honored by readers that never saw the writer:
    a step present on disk but absent from ``commit.json`` (vote-failed
    leftover on another host, in-flight save) is skipped."""
    d = str(tmp_path / "ckpt")
    with ckpt_lib.RunCheckpointer(d) as c:
        c.save(_FakeState(1))
        c.save(_FakeState(2))
    record = os.path.join(d, "commit.json")
    assert json.load(open(record))["committed"] == [1, 2]
    json.dump({"committed": [1]}, open(record, "w"))
    assert ckpt_lib.latest_step(d) == 1
    got = ckpt_lib.restore_checkpoint(d, _FakeState(0))
    assert int(got.step) == 1
    # Explicit-step restore stays exact: the caller asked for 2.
    got2 = ckpt_lib.restore_checkpoint(d, _FakeState(0), step=2)
    assert int(got2.step) == 2


def test_sync_and_async_saves_agree_on_disk(tmp_path):
    """Async mode changes *when* a step is finalized, not *what* is
    saved: both modes leave a committed, structurally intact step whose
    restore is bit-identical. (Exact file lists can't be compared —
    ocdbt names data files per write.)"""
    ds, da = str(tmp_path / "sync"), str(tmp_path / "async")
    with ckpt_lib.RunCheckpointer(ds) as c:
        c.save(_FakeState(3))
    with ckpt_lib.RunCheckpointer(da, async_save=True) as c:
        c.save(_FakeState(3))       # finalized by close()'s barrier

    for d in (ds, da):
        assert ckpt_lib._step_intact(d, 3)
        assert json.load(open(os.path.join(
            d, "commit.json")))["committed"] == [3]
    rs = ckpt_lib.restore_checkpoint(ds, _FakeState(0))
    ra = ckpt_lib.restore_checkpoint(da, _FakeState(0))
    assert int(rs.step) == int(ra.step) == 3
    for a, b in zip(jax.tree.leaves(ckpt_lib._arrays_of(rs)),
                    jax.tree.leaves(ckpt_lib._arrays_of(ra))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- non-finite step guard ----------------------------------------------


def _batch(batch_size=2, seed=0):
    rng = np.random.default_rng(seed)
    img1 = rng.uniform(0, 255, (batch_size, H, W, 3)).astype(np.float32)
    img2 = np.roll(img1, 2, axis=2)
    flow = np.zeros((batch_size, H, W, 2), np.float32)
    flow[..., 0] = 2.0
    valid = np.ones((batch_size, H, W), np.float32)
    return {"image1": img1, "image2": img2, "flow": flow, "valid": valid}


def test_nan_step_skipped_params_unchanged():
    """An injected non-finite loss suppresses the whole update (params,
    opt state, BN stats), counts the skip, and the following finite
    step proceeds normally."""
    tcfg, _, state = _tiny_state()
    set_injector(FaultInjector(nan_loss_steps=(0,)))
    step_fn = make_train_step(tcfg, donate=False)
    rng = jax.random.PRNGKey(1)
    batch = _batch()

    state1, metrics = step_fn(state, batch, rng)
    metrics = jax.device_get(metrics)
    assert metrics["skipped_steps"] == 1.0
    assert not np.isfinite(metrics["loss"])
    assert int(state1.step) == 1               # batch counter advances
    for old, new in zip(jax.tree.leaves(state.params),
                        jax.tree.leaves(state1.params)):
        np.testing.assert_array_equal(np.asarray(old), np.asarray(new))

    # step 1 is not poisoned: the update applies and is finite
    state2, metrics2 = step_fn(state1, batch, rng)
    metrics2 = jax.device_get(metrics2)
    assert metrics2["skipped_steps"] == 0.0
    assert np.isfinite(metrics2["loss"])
    diffs = [not np.array_equal(np.asarray(o), np.asarray(n))
             for o, n in zip(jax.tree.leaves(state1.params),
                             jax.tree.leaves(state2.params))]
    assert any(diffs)
    assert all(np.all(np.isfinite(np.asarray(l)))
               for l in jax.tree.leaves(state2.params))


def test_guarded_step_bit_identical_without_faults():
    """Acceptance criterion: with no faults injected the guarded step's
    numerics are bit-identical to the unguarded one."""
    tcfg, _, state = _tiny_state()
    rng = jax.random.PRNGKey(1)
    batch = _batch()
    guarded_fn = make_train_step(tcfg, donate=False)
    plain_fn = make_train_step(tcfg, donate=False, guard_nonfinite=False)

    g_state, g_metrics = guarded_fn(state, batch, rng)
    p_state, p_metrics = plain_fn(state, batch, rng)
    assert jax.device_get(g_metrics)["skipped_steps"] == 0.0
    for a, b in zip(jax.tree.leaves(g_state.params),
                    jax.tree.leaves(p_state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(g_state.opt_state),
                    jax.tree.leaves(p_state.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (jax.device_get(g_metrics)["loss"]
            == jax.device_get(p_metrics)["loss"])


# -- loader fault recovery ----------------------------------------------


class ArrayDataset:
    """In-memory dataset: sample i's images are constant i."""

    def __init__(self, n=8, h=16, w=24):
        self.n, self.h, self.w = n, h, w

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        img = np.full((self.h, self.w, 3), float(i), np.float32)
        flow = np.zeros((self.h, self.w, 2), np.float32)
        valid = np.ones((self.h, self.w), np.float32)
        return img, img.copy(), flow, valid


def test_loader_substitutes_corrupt_sample(capsys):
    from raft_tpu.data.datasets import DataLoader

    set_injector(FaultInjector(corrupt_sample_indices=frozenset({2})))
    loader = DataLoader(ArrayDataset(n=8), batch_size=4, shuffle=False,
                        num_workers=2, stall_timeout=0)
    batches = list(loader)
    assert len(batches) == 2                     # the epoch completes
    # sample 2 deterministically replaced by its neighbor, sample 3
    got = sorted(batches[0]["image1"][:, 0, 0, 0].tolist())
    assert got == [0.0, 1.0, 3.0, 3.0]
    assert loader.stats.substituted_samples == 1
    assert "substituted" in capsys.readouterr().out


def test_loader_gives_up_when_everything_is_corrupt():
    from raft_tpu.data.datasets import _read_sample

    set_injector(FaultInjector(
        corrupt_sample_indices=frozenset(range(8))))
    with pytest.raises(RuntimeError, match="consecutive samples"):
        _read_sample(ArrayDataset(n=8), 0, retries=0, base_delay=0.001,
                     max_substitutions=3)


def test_read_sample_retries_transient_then_succeeds():
    from raft_tpu.data.datasets import _read_sample

    class FlakyOnce(ArrayDataset):
        def __init__(self):
            super().__init__(n=4)
            self.failures = {1: 1}   # index 1 fails once, then reads

        def __getitem__(self, i):
            if self.failures.get(i, 0) > 0:
                self.failures[i] -= 1
                raise OSError("transient blip")
            return super().__getitem__(i)

    sample, subs, retries = _read_sample(FlakyOnce(), 1, retries=2,
                                         base_delay=0.001)
    assert subs == 0                             # retried, NOT substituted
    assert retries == 1                          # ...and counted as such
    assert sample[0][0, 0, 0] == 1.0


# -- logger counters -----------------------------------------------------


def test_logger_streams_degradation_counters(tmp_path):
    logger = TrainLogger(str(tmp_path / "run"), sum_freq=2,
                         tensorboard=False)
    logger.push({"loss": 1.0, "skipped_steps": 1.0,
                 "substituted_samples": 2.0}, lr=0.1)
    logger.push({"loss": 3.0, "skipped_steps": 0.0,
                 "substituted_samples": 1.0}, lr=0.1)   # flush
    logger.push({"loss": 1.0, "skipped_steps": 1.0,
                 "substituted_samples": 0.0}, lr=0.1)
    logger.push({"loss": 1.0, "skipped_steps": 0.0,
                 "substituted_samples": 0.0}, lr=0.1)   # flush
    logger.close()
    lines = [json.loads(l) for l in
             open(tmp_path / "run" / "scalars.jsonl")]
    # run totals, not window means; loss still window-averaged
    assert lines[0]["skipped_steps"] == 1.0
    assert lines[0]["substituted_samples"] == 3.0
    assert lines[0]["loss"] == pytest.approx(2.0)
    assert lines[1]["skipped_steps"] == 2.0
    assert lines[1]["substituted_samples"] == 3.0


# -- train-loop integration ---------------------------------------------


class SyntheticLoader:
    """Batches with a constant 2px rightward flow (8 = mesh batch)."""

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


def _train_cfg(num_steps, **kw):
    base = dict(name="t", num_steps=num_steps, batch_size=8,
                image_size=(H, W), iters=2, val_freq=1000, sum_freq=2)
    base.update(kw)
    return TrainConfig(**base), RAFTConfig(small=True, iters=2)


@pytest.mark.slow
def test_preemption_during_validation_checkpoints_promptly(tmp_path,
                                                           monkeypatch):
    """A SIGTERM landing inside the val_freq validation block is acted
    on right after validation — the loop must not pull and train
    another batch first."""
    import raft_tpu.evaluate as evaluate_mod
    import raft_tpu.train as train_mod

    tcfg, mcfg = _train_cfg(num_steps=50, val_freq=2)
    box = [None]

    class SpyGuard(train_mod._PreemptionGuard):
        def __init__(self):
            super().__init__()
            box[0] = self

    class CountingLoader(SyntheticLoader):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.count = 0

        def __iter__(self):
            for batch in super().__iter__():
                self.count += 1
                yield batch

    def fake_validation(predictor, names):
        box[0].requested = True        # the signal lands mid-validation
        return {"fake_epe": 1.0}

    monkeypatch.setattr(train_mod, "_PreemptionGuard", SpyGuard)
    monkeypatch.setattr(evaluate_mod, "FlowPredictor",
                        lambda *a, **k: None)
    monkeypatch.setattr(evaluate_mod, "run_validation", fake_validation)

    loader = CountingLoader(n=50)
    state = train_mod.train(
        tcfg, mcfg, ckpt_dir=str(tmp_path / "ckpts"),
        log_dir=str(tmp_path / "logs"), dataloader=loader,
        validation=("sintel",),
        logger=TrainLogger(str(tmp_path / "logs" / "t"), sum_freq=2,
                           tensorboard=False))
    assert int(state.step) == 2
    assert loader.count == 2           # no extra batch after the signal
    assert ckpt_lib.latest_step(str(tmp_path / "ckpts" / "t")) == 2


@pytest.mark.slow
def test_train_aborts_after_consecutive_nan_steps(tmp_path):
    """Persistent divergence: every step non-finite -> the loop skips N
    consecutive updates, checkpoints the last finite state, raises."""
    from raft_tpu.train import train

    tcfg, mcfg = _train_cfg(num_steps=50, max_consecutive_skips=3)
    set_injector(FaultInjector(nan_loss_steps=tuple(range(64))))
    with pytest.raises(TrainingDiverged, match="3 consecutive"):
        train(tcfg, mcfg, ckpt_dir=str(tmp_path / "ckpts"),
              log_dir=str(tmp_path / "logs"),
              dataloader=SyntheticLoader(n=50),
              logger=TrainLogger(str(tmp_path / "logs" / "t"),
                                 sum_freq=2, tensorboard=False))
    # the checkpointed state is the last finite one: the third skip is
    # read with step 4 already in flight, which is finished (skipped
    # too) before the save
    d = str(tmp_path / "ckpts" / "t")
    step = ckpt_lib.latest_step(d)
    assert step == 4                   # step counter advanced 4 skips
    _, _, fresh = _tiny_state(seed=1)
    restored = ckpt_lib.restore_checkpoint(d, fresh)
    assert all(np.all(np.isfinite(np.asarray(l)))
               for l in jax.tree.leaves(restored.params))


@pytest.mark.slow
def test_fault_drill_script():
    """The CI drill: every fault class injected in sequence into a tiny
    run; nonzero exit = a recovery path regressed."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "scripts", "fault_drill.py")],
        capture_output=True, text=True, env=env, timeout=1800)
    assert proc.returncode == 0, proc.stdout + proc.stderr
