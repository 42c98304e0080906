"""REAL multi-process distributed tests: two coordinated interpreters.

The sharding suite runs SPMD semantics on one process with 8 virtual
devices; these tests additionally prove the *multi-host* machinery —
``jax.distributed`` bootstrap, rank gating, cross-process metric
reduction, the train loop's preemption vote, and a full sharded train
step with the batch split across hosts — against two actual processes
wired through a coordinator, the way a TPU pod runs (the reference's
dormant NCCL/DDP scaffolding, ``core/utils/misc.py:366-460``, never had
any test at all, SURVEY.md §4.5).

Each child pins the CPU backend with ONE device per process (clearing
any inherited XLA_FLAGS/topology from the outer pytest) and reports
results as a JSON line; the parent asserts on both.  The train-step
fixture (:func:`make_train_fixture`) is imported by the parent AND the
child code strings so their configs cannot drift.
"""

import json
import os
import socket
import subprocess
import sys
import textwrap
import time

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS_DIR = os.path.dirname(os.path.abspath(__file__))

_PRELUDE = textwrap.dedent("""
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = ""          # drop inherited topology flags
    os.environ["COORDINATOR_ADDRESS"] = "localhost:%(port)d"
    import jax
    jax.config.update("jax_platforms", "cpu")
    pid = int(sys.argv[1])
""")

CHILD_HELPERS = _PRELUDE + textwrap.dedent("""
    from raft_tpu.parallel.distributed import (init_distributed,
                                               is_main_process,
                                               reduce_metrics)
    init_distributed(num_processes=2, process_id=pid)
    from raft_tpu.train import _preemption_agreed

    out = {
        "pid": pid,
        "process_count": jax.process_count(),
        "local_devices": jax.local_device_count(),
        "is_main": is_main_process(),
        # each process contributes a different loss; mean must be 2.0
        "reduced": reduce_metrics({"loss": 1.0 + 2.0 * pid}),
        # only process 1 saw the (simulated) SIGTERM; BOTH must agree
        "agreed": _preemption_agreed(pid == 1),
        "agreed_none": _preemption_agreed(False),
    }
    print("RESULT " + json.dumps(out), flush=True)
""")

CHILD_TRAIN = _PRELUDE + textwrap.dedent("""
    from raft_tpu.parallel.distributed import init_distributed
    init_distributed(num_processes=2, process_id=pid)

    from raft_tpu.parallel import make_mesh
    from test_multiprocess import run_one_step

    # Cheap capability probe BEFORE the expensive model compile: some
    # jaxlib builds (this container's CPU backend) cannot run
    # cross-process XLA computations at all — the host-side machinery
    # (coordination-service votes, KV gathers, orbax barriers) still
    # works there, but a sharded train step cannot. Report honestly
    # and let the parent skip.
    try:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("capability probe")
    except Exception as e:
        if "Multiprocess computations aren't implemented" in str(e):
            print("RESULT " + json.dumps(
                {"pid": pid, "unsupported": True}), flush=True)
            sys.exit(0)
        raise

    mesh = make_mesh()                      # 2 global devices, 1/process
    assert mesh.devices.size == 2, mesh.devices
    with mesh:
        state2, metrics = run_one_step(mesh=mesh)
    out = {"pid": pid, "loss": float(metrics["loss"]),
           "grad_norm": float(metrics["grad_norm"]),
           "step": int(state2.step)}
    print("RESULT " + json.dumps(out), flush=True)
""")


def make_train_fixture():
    """Shared tiny train setup: identical for the single-process ground
    truth and every distributed child (same seeds, same batch)."""
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.models.raft import RAFT

    H, W = 64, 96
    tcfg = TrainConfig(batch_size=2, image_size=(H, W), num_steps=10,
                       iters=2)
    model = RAFT(RAFTConfig(small=True, iters=2))
    g = np.random.default_rng(0)
    batch = {
        "image1": jnp.asarray(g.uniform(0, 255, (2, H, W, 3)),
                              jnp.float32),
        "image2": jnp.asarray(g.uniform(0, 255, (2, H, W, 3)),
                              jnp.float32),
        "flow": jnp.asarray(g.normal(size=(2, H, W, 2)) * 2, jnp.float32),
        "valid": jnp.ones((2, H, W), jnp.float32),
    }
    return tcfg, model, batch, (H, W)


def run_one_step(mesh=None):
    """One jitted train step of the shared fixture, optionally sharded."""
    import jax

    from raft_tpu.parallel import (create_train_state, make_train_step,
                                   shard_batch)

    tcfg, model, batch, shape = make_train_fixture()
    state = create_train_state(jax.random.PRNGKey(0), model, tcfg, shape,
                               mesh=mesh)
    step_fn = make_train_step(tcfg, mesh=mesh, donate=False)
    if mesh is not None:
        batch = shard_batch(batch, mesh)
    state2, metrics = step_fn(state, batch, jax.random.PRNGKey(1))
    jax.block_until_ready(metrics)
    return state2, metrics


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _run_children(template: str, while_they_run=lambda: None):
    """Spawn two coordinated children from ``template``, call
    ``while_they_run()`` in this process meanwhile, and return the
    children's RESULT dicts keyed by pid with what it returned. Both
    children share one deadline, under the test's own limit; on the way
    out, by any path, neither is left running. (The children import this
    module: conftest, which sets the parent's topology flags, is imported
    here and not at the top.)"""
    from conftest import CHILD_TIME_LIMIT as timeout

    child_env = {**os.environ,
                 "PYTHONPATH": os.pathsep.join([REPO_ROOT, TESTS_DIR])}
    code = template % {"port": _free_port()}
    procs = [subprocess.Popen(
        [sys.executable, "-c", code, str(i)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=child_env)
        for i in range(2)]
    deadline = time.monotonic() + timeout
    results = {}
    try:
        here = while_they_run()
        for p in procs:
            try:
                out, _ = p.communicate(
                    timeout=max(deadline - time.monotonic(), 1.0))
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                out, _ = p.communicate()
                pytest.fail(f"distributed child over its {timeout} s "
                            f"(coordinator hang?):\n{out[-2000:]}")
            assert p.returncode == 0, out[-2000:]
            lines = [ln for ln in out.splitlines()
                     if ln.startswith("RESULT ")]
            assert lines, f"no RESULT line in child output:\n{out[-2000:]}"
            r = json.loads(lines[-1][len("RESULT "):])
            results[r["pid"]] = r
    finally:
        for q in procs:
            if q.poll() is None:
                q.kill()
                q.wait()
    assert set(results) == {0, 1}
    return results, here


def test_two_process_distributed_helpers():
    results, _ = _run_children(CHILD_HELPERS)
    for pid, r in results.items():
        assert r["process_count"] == 2
        assert r["local_devices"] == 1
        assert r["is_main"] == (pid == 0)
        # cross-process mean of (1.0, 3.0)
        assert abs(r["reduced"]["loss"] - 2.0) < 1e-6
        # preemption vote: one host's signal stops both; quiet == go on
        assert r["agreed"] is True
        assert r["agreed_none"] is False


def test_two_process_sharded_train_step():
    """One jitted train step over a 2-process global mesh (1 device per
    process, batch sharded across hosts) — THE multi-host scaling path.
    Both hosts must agree on the loss, and it must match a single-process
    run of the same step to float tolerance."""
    import numpy as np

    # the single-process ground truth is computed while the children run
    results, (_, m_single) = _run_children(
        CHILD_TRAIN, lambda: run_one_step(mesh=None))
    if any(r.get("unsupported") for r in results.values()):
        pytest.skip("jaxlib backend lacks cross-process XLA computations "
                    "(CPU multiprocess); host-side distributed machinery "
                    "is covered by test_two_process_distributed_helpers")
    assert results[0]["step"] == results[1]["step"] == 1
    # replicated metrics: both hosts computed the same global loss
    assert abs(results[0]["loss"] - results[1]["loss"]) < 1e-6

    np.testing.assert_allclose(results[0]["loss"],
                               float(m_single["loss"]), rtol=2e-4)
