"""Training metrics: smoothed meters, periodic console status, scalar sinks.

Equivalents of the reference's observability stack:

* :class:`SmoothedValue` / :class:`MetricLogger` — the vendored DETR meters
  (reference ``core/utils/misc.py:61-120, :193-280``), with the distributed
  sync expressed as a jax ``process_allgather`` instead of
  ``torch.distributed.all_reduce``.
* :class:`TrainLogger` — the trainer's ``Logger`` (reference
  ``train.py:127-168``): running means printed every ``SUM_FREQ`` steps with
  the current LR, plus scalar time-series sinks. Scalars always stream to a
  JSONL file (greppable, dependency-free) AND to TensorBoard event files —
  via ``torch.utils.tensorboard`` when torch is importable (used exactly
  like the reference uses ``SummaryWriter``), else via the self-contained
  ``raft_tpu.utils.tb_events.EventWriter`` (same on-disk format, zero
  dependencies), so the reference's artifact format is always produced.
"""

from __future__ import annotations

import json
import os
import time
from collections import defaultdict, deque
from statistics import fmean
from typing import Dict, Iterable, Optional

from raft_tpu.utils.profiling import (host_timer, slow_unit_lines,
                                       unit_accounts)


class SmoothedValue:
    """Window-smoothed scalar with global average
    (reference ``core/utils/misc.py:61-120``)."""

    def __init__(self, window_size: int = 20, fmt: str = "{median:.4f} "
                 "({global_avg:.4f})"):
        self.deque: deque = deque(maxlen=window_size)
        self.total = 0.0
        self.count = 0
        self.fmt = fmt

    def update(self, value, n: int = 1):
        value = float(value)
        self.deque.append(value)
        self.count += n
        self.total += value * n

    def synchronize_between_processes(self):
        """Pool count/total across hosts (reference ``:79-90``); no-op for
        single-process runs."""
        import jax

        if jax.process_count() == 1:
            return
        from jax.experimental import multihost_utils
        import numpy as np

        arr = multihost_utils.process_allgather(
            np.asarray([self.count, self.total], np.float64))
        self.count = int(arr[:, 0].sum())
        self.total = float(arr[:, 1].sum())

    @property
    def median(self) -> float:
        d = sorted(self.deque)
        return d[len(d) // 2] if d else 0.0

    @property
    def avg(self) -> float:
        return sum(self.deque) / len(self.deque) if self.deque else 0.0

    @property
    def global_avg(self) -> float:
        return self.total / max(self.count, 1)

    @property
    def max(self) -> float:
        return max(self.deque) if self.deque else 0.0

    @property
    def value(self) -> float:
        return self.deque[-1] if self.deque else 0.0

    def __str__(self):
        return self.fmt.format(median=self.median, avg=self.avg,
                               global_avg=self.global_avg, max=self.max,
                               value=self.value)


class MetricLogger:
    """Meter collection + timed iteration logging
    (reference ``core/utils/misc.py:193-280``)."""

    def __init__(self, delimiter: str = "  "):
        self.meters: Dict[str, SmoothedValue] = defaultdict(SmoothedValue)
        self.delimiter = delimiter

    def update(self, **kwargs):
        for k, v in kwargs.items():
            self.meters[k].update(float(v))

    def __getattr__(self, attr):
        if attr in self.meters:
            return self.meters[attr]
        raise AttributeError(attr)

    def __str__(self):
        return self.delimiter.join(
            f"{name}: {meter}" for name, meter in self.meters.items())

    def synchronize_between_processes(self):
        for meter in self.meters.values():
            meter.synchronize_between_processes()

    def add_meter(self, name: str, meter: SmoothedValue):
        self.meters[name] = meter

    def log_every(self, iterable: Iterable, print_freq: int,
                  header: str = ""):
        i = 0
        start = time.time()
        iter_time = SmoothedValue(fmt="{avg:.4f}")
        data_time = SmoothedValue(fmt="{avg:.4f}")
        end = time.time()
        for obj in iterable:
            data_time.update(time.time() - end)
            yield obj
            iter_time.update(time.time() - end)
            if i % print_freq == 0:
                print(self.delimiter.join([
                    header, f"[{i}]", str(self),
                    f"time: {iter_time}", f"data: {data_time}"]))
            i += 1
            end = time.time()
        total = time.time() - start
        print(f"{header} Total time: {total:.1f}s "
              f"({total / max(i, 1):.4f} s / it)")


class _JsonlWriter:
    def __init__(self, path: str):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._f = open(path, "a", buffering=1)

    def add_scalars(self, step: int, scalars: Dict[str, float]):
        self._f.write(json.dumps({"step": step, **scalars}) + "\n")

    def close(self):
        self._f.close()


class TrainLogger:
    """The trainer's periodic status printer + scalar sinks
    (reference ``train.py:127-168``).

    Args:
      log_dir: run directory; scalars go to ``log_dir/scalars.jsonl`` and
        (if available) TensorBoard event files.
      sum_freq: console/scalar flush period (reference SUM_FREQ=100).
    """

    # Degradation counters (non-finite steps skipped by the train-step
    # guard, unreadable samples substituted by the loader): accumulated
    # as RUN TOTALS rather than window means and emitted with every
    # scalar flush, so a run can be audited for silent degradation
    # from its JSONL/TensorBoard stream alone.
    COUNTER_KEYS = ("skipped_steps", "substituted_samples")

    def __init__(self, log_dir: str, sum_freq: int = 100,
                 tensorboard: bool = True):
        self.log_dir = log_dir
        self.sum_freq = sum_freq
        self.total_steps = 0
        self.running: Dict[str, float] = {}
        self.counters: Dict[str, float] = {}
        self._jsonl = _JsonlWriter(os.path.join(log_dir, "scalars.jsonl"))
        self._tb = None
        if tensorboard:
            try:
                from torch.utils.tensorboard import SummaryWriter
                self._tb = SummaryWriter(log_dir=log_dir)
            except Exception:
                # torch-free hosts still get the reference's artifact
                # format: a self-contained events.out.tfevents writer
                # (raft_tpu/utils/tb_events.py) with the add_scalar/
                # add_image subset the logger uses.
                from raft_tpu.utils.tb_events import EventWriter
                self._tb = EventWriter(log_dir)
        self._t0 = time.time()
        self._host_mark = host_timer().summary()
        self._spans_mark = host_timer().recorded
        self._last_step_id = 0
        # The same run totals, live on the process telemetry registry
        # (one labeled gauge family; the JSONL/TensorBoard stream stays
        # the canonical artifact — this is the scrape surface).
        try:
            from raft_tpu.observability import get_registry
            get_registry().gauge(
                "train_counters",
                help="run-total degradation counters from the train "
                     "logger",
                labelnames=("counter",),
                fn=lambda: ({(k,): float(v)
                             for k, v in self.counters.items()}
                            or {(k,): 0.0 for k in self.COUNTER_KEYS}))
        except ValueError:
            # A second TrainLogger in one process (tests): the family
            # already exists; the first logger keeps the binding.
            pass

    def _status(self, lr: Optional[float]) -> str:
        rate = self.sum_freq / max(time.time() - self._t0, 1e-9)
        parts = [f"[{self.total_steps + 1:6d}"]
        parts.append(f"lr {lr:10.7f}]" if lr is not None else "]")
        parts += [f"{k}: {v / self.sum_freq:10.4f}"
                  for k, v in sorted(self.running.items())]
        parts += [f"{k}: {v:g}" for k, v in sorted(self.counters.items())
                  if v]
        parts.append(f"({rate:.2f} it/s)")
        return " ".join(parts)

    def push(self, metrics: Dict[str, float], lr: Optional[float] = None):
        """Accumulate one step's metrics; print + flush every sum_freq.

        Keys in :attr:`COUNTER_KEYS` are treated as per-step increments
        of run-total degradation counters (not window-averaged).
        """
        self.total_steps += 1
        for k, v in metrics.items():
            if k in self.COUNTER_KEYS:
                self.counters[k] = self.counters.get(k, 0.0) + float(v)
            else:
                self.running[k] = self.running.get(k, 0.0) + float(v)
        if self.total_steps % self.sum_freq == 0:
            account, slow = self._host_step_account()
            ahead = (f" | dispatched ahead: {account['host/ahead_steps']:g} "
                     f"of {account['host/steps']:g} steps" if account
                     else "")
            print(self._status(lr) + ahead)
            scalars = {k: v / self.sum_freq for k, v in self.running.items()}
            if lr is not None:
                scalars["lr"] = lr
            scalars.update(self.counters)
            scalars.update(self._host_stage_means())
            scalars.update(account)
            for line in slow:
                print(line)
            self.write_dict(scalars)
            self.running = {}
            self._t0 = time.time()

    def _host_stage_means(self) -> Dict[str, float]:
        """``host/<stage>_ms``: the mean of each of the train loop's
        host spans (``train.*`` in the process host timer) that closed
        since the last flush."""
        timer = host_timer()
        new = timer.summary(since=self._host_mark)
        self._host_mark = timer.summary()
        return {f"host/{name[len('train.'):]}_ms": row["mean_ms"]
                for name, row in new.items() if name.startswith("train.")}

    def _host_step_account(self):
        """The steps that closed since the last flush, from the ring
        (the step that is flushing is still open: the next flush's):
        ``host/unattributed_ms`` (step time under no stage span),
        ``host/collector_ms`` (the cyclic collector's, on any thread),
        ``host/cpu_ms`` (the loop's thread on a CPU), each a mean,
        ``host/worst_step_ms``, and how many steps completed and of them
        how many were already in flight when their step began and had the
        next one dispatched ahead (``host/steps``,
        ``host/ahead_steps``); and a line for each step that ran over
        three times their median. Off the hot path: only the spans
        recorded since the last flush, and the step before them, are
        read."""
        timer = host_timer()
        recorded = timer.recorded
        # 16 more: the step before, which the first new one's CPU time
        # is counted from
        rows = unit_accounts(
            timer.spans(newest=recorded - self._spans_mark + 16),
            "train.step")
        rows = [r for r in rows if r["id"] > self._last_step_id]
        self._spans_mark = recorded
        if not rows:
            return {}, []
        self._last_step_id = rows[-1]["id"]
        scalars = {
            "host/unattributed_ms": fmean(r["unattributed"] for r in rows),
            "host/collector_ms": fmean(r["collector_ms"] for r in rows),
            "host/worst_step_ms": max(r["ms"] for r in rows),
            "host/steps": float(sum(r["complete"] for r in rows)),
            "host/ahead_steps": float(sum(r["ahead"] for r in rows))}
        cpu = [r["cpu_ms"] for r in rows if r["cpu_ms"] is not None]
        if cpu:
            scalars["host/cpu_ms"] = fmean(cpu)
        return scalars, slow_unit_lines(rows, "step")

    def write_images(self, image1, image2, flow_gt, flow_preds,
                     sparse_preds=None, phase: str = "T",
                     step: Optional[int] = None, max_samples: int = 10):
        """Render and sink training image panels (reference
        ``train.py:170-334``): flow rows for both families, keypoint/
        confidence circles and attention-mask overlays for the sparse
        family.  Panels go to TensorBoard (when available) AND to PNGs
        under ``log_dir/images/`` so headless runs keep the evidence.

        All array args are host numpy, NHWC, images in [0, 255];
        ``flow_preds`` is (iters, B, H, W, 2) or a per-iteration list;
        ``sparse_preds`` the sparse family's per-iteration batched
        ``(ref_points, key_flows, masks, scores)`` tuples, or None.
        """
        from raft_tpu.utils.image_panels import render_panels

        step = step if step is not None else self.total_steps
        panels = render_panels(image1, image2, flow_gt, flow_preds,
                               sparse_preds, max_samples=max_samples,
                               seed=step)
        img_dir = os.path.join(self.log_dir, "images")
        os.makedirs(img_dir, exist_ok=True)
        for i, panel in enumerate(panels):
            name = f"{phase}_Image_{i + 1:02d}"
            if self._tb is not None:
                try:
                    self._tb.add_image(name, panel, step,
                                       dataformats="HWC")
                except Exception as e:   # TB image sink is best-effort
                    # EventWriter.add_image needs Pillow for the PNG
                    # encode; a Pillow-free host should skip TB images,
                    # not die mid-training (the scalar sinks still run).
                    print(f"WARNING: TensorBoard image write failed: {e}")
            try:
                from PIL import Image
                Image.fromarray(panel).save(
                    os.path.join(img_dir, f"{step:08d}_{name}.png"))
            except Exception as e:   # PNG sink is best-effort
                print(f"WARNING: image panel PNG write failed: {e}")
        return len(panels)

    def write_dict(self, results: Dict[str, float],
                   step: Optional[int] = None):
        step = step if step is not None else self.total_steps
        self._jsonl.add_scalars(step, {k: float(v)
                                       for k, v in results.items()})
        if self._tb is not None:
            for k, v in results.items():
                self._tb.add_scalar(k, float(v), step)

    def close(self):
        self._jsonl.close()
        if self._tb is not None:
            self._tb.close()
