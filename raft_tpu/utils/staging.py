"""Recycled host staging buffers for stacked model inputs.

One pool for the repo: the serving engine's dispatch thread and the
dataset pass (``evaluate._predict_dataset``) both write each frame once,
straight into its batch slot of a buffer that outlives the batch,
instead of allocating a padded frame per request and a stack per batch.
On the chip's host a Sintel batch of 128 pairs (1.38 GB) took 1.4 s to
``np.stack`` into fresh pages, nearly all of it page faults, against
0.12-0.13 s to pad the same frames into buffers that were already
mapped (PERF.md, Findings of PR 28).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Tuple

import numpy as np


class StagingArena:
    """Per-(shape, dtype) pool of preallocated host staging buffers —
    the zero-copy replacement for per-batch pad-then-stack allocation.

    The staging thread ``acquire``s one buffer per stacked input,
    writes each request's frame ONCE directly into its batch slot (a
    single memcpy per request; no intermediate padded array, no
    ``np.stack`` allocation per batch), and the buffer stays with the
    batch until its outputs have been synced — only then is it
    ``release``d back to the pool, so recycling can never overwrite
    bytes a transfer or an executable might still read
    (donation-compatible: donation consumes the *device* copy, never
    the host buffer). Every slot — tail-pad included — is rewritten on
    each acquire-fill cycle, so stale bytes from the previous batch
    can't leak. Buffers from failed batches are dropped, not pooled
    (the rare path keeps no aliasing questions open).

    ``allocated`` counts the buffers ``acquire`` had to create because
    the pool held none of that key: a caller reads it before and after
    to learn whether its batch ran on recycled memory.
    """

    # Per-key cap: pipeline_depth batches in flight + one being staged
    # covers the engine's steady state, and the dataset pass's two pairs
    # a shape (one batch on the device, one filling) are exactly four;
    # beyond that, fall back to allocation rather than hold unbounded
    # idle buffers.
    _MAX_PER_KEY = 4

    def __init__(self):
        self._pools: Dict[Tuple, List[np.ndarray]] = {}
        self._lock = threading.Lock()
        self.allocated = 0

    def acquire(self, shape: Tuple, dtype) -> np.ndarray:
        key = (tuple(int(s) for s in shape), np.dtype(dtype).str)
        with self._lock:
            pool = self._pools.get(key)
            if pool:
                return pool.pop()
            self.allocated += 1
        return np.empty(key[0], dtype)

    def release(self, *buffers) -> None:
        for b in buffers:
            if b is None:
                continue
            key = (b.shape, b.dtype.str)
            with self._lock:
                pool = self._pools.setdefault(key, [])
                if len(pool) < self._MAX_PER_KEY:
                    pool.append(b)

    def pooled_buffers(self) -> int:
        with self._lock:
            return sum(len(p) for p in self._pools.values())
