"""Multi-host bootstrap and cross-process helpers.

TPU-native replacement for the reference's dormant NCCL/DDP scaffolding
(``core/utils/misc.py:366-460``): on TPU pods, ``jax.distributed.initialize``
wires up all hosts; collectives are compiled into the sharded program (ICI
within a slice, DCN across slices), so there is no process group, backend
choice, or pickle-based ``all_gather`` to reimplement. What remains useful —
rank discovery, master-only side effects, cross-host metric reduction — is
provided here.
"""

from __future__ import annotations

import itertools
import json
import os
from typing import Dict, Optional

import jax
import numpy as np

from raft_tpu.resilience import all_hosts_agree


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Initialize multi-host JAX (reference ``init_distributed_mode``,
    ``core/utils/misc.py:422-460``).

    On TPU pods all arguments are auto-detected from the metadata server;
    explicit args cover the env-var path (``COORDINATOR_ADDRESS`` etc.) the
    way the reference read ``RANK``/``WORLD_SIZE``. Safe to call on a
    single host (no-op).

    The already-initialized check must NOT touch ``jax.process_count()``
    (or any device API): that would initialize the XLA backend first and
    make ``jax.distributed.initialize`` unconditionally fail — the
    coordinator client state is inspected instead.
    """
    if jax.distributed.is_initialized():
        return  # already initialized
    env = os.environ
    if coordinator_address is None:
        coordinator_address = env.get("COORDINATOR_ADDRESS")
    if coordinator_address is None and "JAX_COORDINATOR" not in env:
        # Single-process run (the common case on one chip / CPU tests).
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes, process_id=process_id)


def is_main_process() -> bool:
    """Reference ``is_main_process`` (``core/utils/misc.py:410-412``)."""
    return jax.process_index() == 0


def save_on_master(save_fn, *args, **kwargs) -> bool:
    """Run a side-effecting save only on rank 0
    (reference ``core/utils/misc.py:417-419``).

    Routes through :func:`raft_tpu.resilience.all_hosts_agree`: every
    host learns whether the master's save actually succeeded (and the
    vote doubles as a fence — no host races ahead of a save that is
    still failing). Returns that agreed success flag on every host; the
    master additionally re-raises its own exception after voting, so
    the pod never deadlocks on a master that died silently mid-save.
    Single process: plain call, exceptions propagate as before.
    """
    err = None
    if is_main_process():
        try:
            save_fn(*args, **kwargs)
        except Exception as e:      # vote first, raise after — a
            err = e                 # pre-vote raise would desync hosts
    agreed = all_hosts_agree(err is None)
    if err is not None:
        raise err
    return agreed


def reduce_metrics(metrics: Dict[str, jax.Array],
                   average: bool = True) -> Dict[str, float]:
    """Cross-host mean of already-device-reduced scalars
    (reference ``reduce_dict``, ``core/utils/misc.py:166-190``).

    Under jit-with-sharding the per-step metrics are already global over the
    mesh; this helper exists for host-side aggregation of *python* scalars
    across processes (e.g. validation loops that iterate different shards of
    a dataset per host).
    """
    if jax.process_count() == 1:
        return {k: float(v) for k, v in metrics.items()}
    keys = sorted(metrics.keys())
    vec = np.asarray([float(metrics[k]) for k in keys], np.float64)
    rows = _host_allgather_floats(vec)
    summed = np.sum(rows, axis=0)
    if average:
        summed = summed / jax.process_count()
    return {k: float(summed[i]) for i, k in enumerate(keys)}


_GATHER_SEQ = itertools.count()
_GATHER_TIMEOUT_MS = 600_000


def _host_allgather_floats(vec: np.ndarray) -> np.ndarray:
    """All-gather one float vector per process on the *host* side.

    Python scalars don't need a device collective; the coordination
    service's key-value store carries them (same channel as
    :func:`raft_tpu.resilience.all_hosts_agree` votes), which also
    works on backends without cross-process XLA computation support
    (CPU multi-process drills/tests). Falls back to
    ``process_allgather`` when no coordination client exists. Like
    every cross-host helper here, each call consumes a sequence number
    and must happen at the same point on every process.
    """
    from raft_tpu.resilience import _coordination_client

    client = _coordination_client()
    if client is None:
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(
            vec.astype(np.float32)))
    key = f"raft_tpu/gather/{next(_GATHER_SEQ)}"
    client.key_value_set(f"{key}/{jax.process_index()}",
                         json.dumps([float(x) for x in vec]))
    return np.asarray([
        json.loads(client.blocking_key_value_get(
            f"{key}/{i}", _GATHER_TIMEOUT_MS))
        for i in range(jax.process_count())])
