"""Traffic kind ``train_steps``: the program's own training loop,
``raft_tpu.train.train()``, fed by a seeded loader that the benchmark
injects through the ``dataloader=`` seam and that is also its clock.

The loader hands out batches built on a background thread. Every fetch
is stamped: ``train()`` fetches batch ``n+1`` only after step ``n``'s
metrics have reached the host, so a fetch marks a completed step. The
first ``warmup_steps`` fetches are set-up (the first compiles); the
window opens at the next fetch and closes at the first fetch
``--seconds`` later, by an exception that ends ``train()`` before its
exit checkpoint. One process, one ``train()`` call, one compiled step
with one state: the steps that ``correct`` follows are the first
``followed_steps`` of that same call.

Two seams that ``train()`` does not offer are made from outside, inside
``observed()``: ``create_train_state`` is wrapped to put the benchmark's
seeded weights in place of the program's own initialisation (what
``restore_ckpt`` does, without a checkpoint on disk), and
``make_train_step``'s result is wrapped to copy the state to the host
after each followed step. Neither changes what a step computes.
"""

from __future__ import annotations

import contextlib
import queue
import tempfile
import threading
import time
from typing import Optional

import numpy as np

from benchmark import harness


class WindowClosed(Exception):
    """Raised by the loader to end ``train()`` when the window is over."""


# ------------------------------------------------------------------ traffic

def make_batches(seed: int, n: int, batch: int, height: int, width: int):
    """``n`` chairs-shaped batches from the seed; every row differs:
    its own texture, its own displacement (which is its ground-truth
    flow), its own band of invalid pixels."""
    rng = np.random.default_rng([seed, 0x7EA1])
    out = []
    for _ in range(n):
        image1 = np.empty((batch, height, width, 3), np.float32)
        image2 = np.empty_like(image1)
        flow = np.empty((batch, height, width, 2), np.float32)
        valid = np.ones((batch, height, width), np.float32)
        for row in range(batch):
            coarse = rng.integers(0, 256, (height // 8 + 4, width // 8 + 4,
                                           3))
            scene = np.kron(coarse, np.ones((8, 8, 1), np.int64))
            dx, dy = rng.integers(-6, 7, 2)
            for frame, (oy, ox) in ((image1, (12, 12)),
                                    (image2, (12 - dy, 12 - dx))):
                crop = scene[oy:oy + height, ox:ox + width]
                noise = rng.integers(0, 77, (height, width, 3))
                frame[row] = np.clip(crop * 7 // 10 + noise, 0, 255)
            flow[row] = (dx, dy)
            flow[row] += rng.normal(0, 0.25, (height, width, 2))
            band = rng.integers(0, height - 16)
            valid[row, band:band + 16] = 0.0
        out.append({"image1": image1, "image2": image2, "flow": flow,
                    "valid": valid})
    return out


class ClockLoader:
    """The injected dataloader. ``train()`` iterates it once; it never
    ends by itself."""

    def __init__(self, pool, *, warmup_steps: int, seconds: float,
                 queue_depth: int, on_window_open=None,
                 on_window_close=None, on_step_done=None):
        self.pool = pool
        self.warmup_steps, self.seconds = warmup_steps, seconds
        self.fetch_s, self.wait_s = [], []
        self.on_open, self.on_close = on_window_open, on_window_close
        self.on_step_done = on_step_done
        self.phases = harness.Phases()
        self._queue: queue.Queue = queue.Queue(maxsize=queue_depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._produce, daemon=True,
                                        name="bench-loader")
        self.window_open_s: Optional[float] = None
        self.window_close_s: Optional[float] = None

    def _produce(self):
        n = 0
        while not self._stop.is_set():
            fresh = {k: np.array(v)
                     for k, v in self.pool[n % len(self.pool)].items()}
            while not self._stop.is_set():
                try:
                    self._queue.put(fresh, timeout=0.1)
                    break
                except queue.Full:
                    continue
            n += 1

    def __iter__(self):
        if self._thread.is_alive() or self.fetch_s:
            raise WindowClosed("the loader is iterated once")
        self._thread.start()
        return self

    def __next__(self):
        now = time.perf_counter()
        steps_done = len(self.fetch_s)
        self.fetch_s.append(now)
        if steps_done == self.warmup_steps:
            self.window_open_s = now
            if self.on_open:
                self.on_open()
        elif (self.window_open_s is not None
              and now - self.window_open_s >= self.seconds):
            self.window_close_s = now
            self.phases.switch(None)
            if self.on_close:
                self.on_close()
            raise WindowClosed()
        elif self.window_open_s is not None and self.on_step_done:
            self.on_step_done(now - self.window_open_s)
        in_window = self.window_open_s is not None
        if in_window:
            self.phases.switch("loader_wait")
        batch = self._queue.get()
        self.wait_s.append(time.perf_counter() - now)
        if in_window:
            self.phases.switch("train_loop_step")
        return batch

    def close(self):
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=10)
        if self._thread.is_alive():
            raise RuntimeError("the loader's thread did not stop")


# ------------------------------------------------------------------- seams

class Observed:
    """What the wrapped step saw: the jitted step itself, the abstract
    arguments of its first call, and host copies of the state after each
    followed step."""

    def __init__(self, followed: int):
        self.followed = followed
        self.step = None
        self.abstract_args = None
        self.initial_params = None
        self.losses, self.params, self.opt_states = [], [], []
        self.calls = 0


@contextlib.contextmanager
def observed(variables, record: Observed, step_fault=None):
    """Inside, ``raft_tpu.train`` builds its state from ``variables``
    and its step reports to ``record``. ``step_fault`` (tests of
    ``correct`` only) breaks the jitted step underneath."""
    import jax
    import jax.numpy as jnp

    import raft_tpu.train as program

    real_create = program.create_train_state
    real_make = program.make_train_step

    def create_train_state(*args, **kwargs):
        state = real_create(*args, **kwargs)
        # a copy: the step donates its state, and the reference needs
        # these arrays after the window
        put = lambda new, old: jax.device_put(    # noqa: E731
            jnp.copy(new), old.sharding)
        return state.replace(
            params=jax.tree.map(put, variables["params"], state.params),
            batch_stats=jax.tree.map(put, variables["batch_stats"],
                                     state.batch_stats))

    def make_train_step(*args, **kwargs):
        step = real_make(*args, **kwargs)
        record.step = step
        if step_fault is not None:
            step = step_fault(step)

        def observed_step(state, batch, rng):
            if record.calls == 0:
                record.abstract_args = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(
                        a.shape, a.dtype, sharding=a.sharding),
                    (state, batch, rng))
                record.initial_params = jax.device_get(state.params)
            new_state, metrics = step(state, batch, rng)
            if record.calls < record.followed:
                record.losses.append(float(metrics["loss"]))
                record.params.append(jax.device_get(new_state.params))
                record.opt_states.append(
                    jax.device_get(new_state.opt_state))
            record.calls += 1
            return new_state, metrics

        return observed_step

    program.create_train_state = create_train_state
    program.make_train_step = make_train_step
    try:
        yield
    finally:
        program.create_train_state = real_create
        program.make_train_step = real_make


def adam_mu(opt_state):
    """The first-moment tree inside an optax state, wherever it sits."""
    found = []

    def walk(node):
        if hasattr(node, "mu") and hasattr(node, "nu"):
            found.append(node.mu)
        elif isinstance(node, (tuple, list)):
            for child in node:
                walk(child)

    walk(opt_state)
    if len(found) != 1:
        raise ValueError(f"expected one Adam state, found {len(found)}")
    return found[0]


# --------------------------------------------------------------- reference

def leaf_norms(tree):
    import jax
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in jax.tree.leaves(tree)])


def worst_leaf_gap(ours, theirs, keep=None):
    """The widest gap between two sets of per-leaf norms, each measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. ``keep`` leaves some leaves out."""
    scale = np.maximum(theirs, np.median(theirs))
    gaps = np.abs(ours - theirs) / scale
    if keep is not None:
        gaps = np.where(keep, gaps, 0.0)
    return float(gaps.max()), int(gaps.argmax())


def follow_reference(variables, batches, traffic, config,
                     operand_name: str = "identity", fault=None):
    """The plain reference through the first steps: each step's loss,
    the first clipped gradient, and the parameters after the last."""
    import functools

    import jax
    import jax.numpy as jnp

    from benchmark.reference import raft as reference

    step = jax.jit(functools.partial(
        reference.train_step, iters=traffic["iters"], lr=traffic["lr"],
        total_steps=traffic["num_steps"] + 100, wdecay=traffic["wdecay"],
        eps=traffic["epsilon"], clip=traffic["clip"],
        gamma=traffic["gamma"], operand=getattr(reference, operand_name),
        **config["reference"]["kwargs"]))
    params = variables["params"]
    opt = {"mu": jax.tree.map(jnp.zeros_like, params),
           "nu": jax.tree.map(jnp.zeros_like, params)}
    losses, first_grad = [], None
    for n, batch in enumerate(batches):
        if fault is not None:
            batch = fault(batch)
        with jax.default_matmul_precision("highest"):
            new_params, opt, loss, grads = step(params, opt, batch, n)
        if fault is not None and getattr(fault, "after", None):
            new_params = fault.after(params, new_params)
        params = new_params
        losses.append(float(loss))
        if n == 0:
            first_grad = jax.device_get(grads)
    return {"losses": losses, "first_grad": first_grad,
            "params": jax.device_get(params)}


def compare_steps(initial, ours, theirs) -> dict:
    """``ours`` and ``theirs``: ``losses``, ``first_grad`` and final
    ``params`` of the program (or what stands in its place) and of the
    reference. Leaves whose reference gradient is nought to rounding
    (under a thousandth of the median leaf's) are left out of the two
    worst-leaf numbers: the optimizer moves them by round-off alone."""
    import jax

    ref_grad = leaf_norms(theirs["first_grad"])
    keep = ref_grad >= 1e-3 * np.median(ref_grad)
    our_grad = leaf_norms(ours["first_grad"])
    change = lambda tree: leaf_norms(jax.tree.map(        # noqa: E731
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        tree, initial))
    ours_change, theirs_change = change(ours["params"]), change(
        theirs["params"])
    grad_gap, grad_leaf = worst_leaf_gap(our_grad, ref_grad, keep)
    change_gap, change_leaf = worst_leaf_gap(ours_change, theirs_change,
                                             keep)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(theirs["first_grad"])[0]]
    return {
        "loss_gaps": [abs(a - b) / abs(b) for a, b in
                      zip(ours["losses"], theirs["losses"])],
        "losses": ours["losses"], "reference_losses": theirs["losses"],
        "grad_norm_gap_worst_leaf": grad_gap,
        "grad_worst_leaf": names[grad_leaf],
        "grad_norm_gap_all_leaves": worst_leaf_gap(our_grad, ref_grad)[0],
        "change_norm_gap_worst_leaf": change_gap,
        "change_worst_leaf": names[change_leaf],
        "change_norm_gap_all_leaves": worst_leaf_gap(
            ours_change, theirs_change)[0],
        "leaves": int(len(keep)), "leaves_left_out": int((~keep).sum()),
        "median_leaf_grad_norm": float(np.median(ref_grad)),
        "median_leaf_change_norm": float(np.median(theirs_change)),
    }


# --------------------------------------------------------------------- run

def configs_of(cell: dict, seed: int):
    """``TrainConfig`` and ``RAFTConfig`` as ``train.py``'s ``main()``
    builds them for this cell (``corr_impl`` resolved as it does)."""
    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.train import resolve_train_corr_engine

    traffic, model = cell["traffic"], dict(cell["config"]["model"])
    hw = (traffic["height"], traffic["width"])
    alternate = resolve_train_corr_engine(
        "raft", cell["config"]["train"]["corr_impl"], False, None,
        model["small"], model["mixed_precision"], hw)
    tcfg = TrainConfig(
        name="benchmark", stage=traffic["stage"], lr=traffic["lr"],
        num_steps=traffic["num_steps"], batch_size=traffic["batch_size"],
        image_size=hw, wdecay=traffic["wdecay"],
        epsilon=traffic["epsilon"], clip=traffic["clip"],
        gamma=traffic["gamma"], iters=traffic["iters"],
        val_freq=10 ** 9, seed=seed % (2 ** 31))
    mcfg = RAFTConfig(iters=traffic["iters"], alternate_corr=alternate,
                      **model)
    return tcfg, mcfg


def seeded_variables(mcfg, seed: int):
    from benchmark import weights
    from raft_tpu.models.raft import RAFT

    return weights.make_variables(weights.variable_shapes(RAFT(mcfg)), seed)


def run(cell: dict, devices, *, seed: int, seconds: float, trace: bool,
        process_start: float, entry=None):
    """Set up, run ``train()`` through warm-up and window, then follow
    the first steps with the reference. ``entry`` (tests of ``correct``
    only) is a fault to plant under the jitted step."""
    import jax

    from benchmark.drivers.dataset_pass import census
    from raft_tpu.train import train

    harness.enable_compile_cache()
    counter = harness.CompileCounter()
    traffic, config = cell["traffic"], cell["config"]
    on_chip = devices[0].platform == "tpu"
    marks = [("imports_and_devices", time.perf_counter())]

    tcfg, mcfg = configs_of(cell, seed)
    variables = seeded_variables(mcfg, seed)
    jax.block_until_ready(variables)
    marks.append(("model_and_weights", time.perf_counter()))
    pool = make_batches(seed, traffic["pool"], traffic["batch_size"],
                        traffic["height"], traffic["width"])
    marks.append(("batch_pool", time.perf_counter()))

    tracer = harness.Trace(trace)
    snapshots = {}

    def window_opens():
        snapshots["setup"] = counter.snapshot()
        snapshots["setup_s"] = time.perf_counter() - process_start

    def step_done(since_open):
        # the trace covers the window's last `trace_seconds`: many
        # thousand device events a step make a longer one slow to read
        if (trace and "traced_from_ns" not in snapshots
                and since_open >= seconds - traffic["trace_seconds"]):
            loader.phases.switch(None)
            tracer.start()
            snapshots["traced_from_ns"] = time.perf_counter_ns()
            snapshots["traced_from_step"] = len(loader.fetch_s)

    def window_closes():
        if tracer.running:
            start = snapshots["traced_from_ns"]
            tracer.stop([["bench.window", start,
                          time.perf_counter_ns() - start]]
                        + [sp for sp in loader.phases.log
                           if sp[1] >= start])
        snapshots["window"] = counter.snapshot()
        snapshots["memory"] = harness.memory_peak(devices)

    loader = ClockLoader(pool, warmup_steps=traffic["warmup_steps"],
                         seconds=seconds,
                         queue_depth=traffic["queue_depth"],
                         on_window_open=window_opens,
                         on_window_close=window_closes,
                         on_step_done=step_done)
    record = Observed(traffic["followed_steps"])
    out_dir = tempfile.mkdtemp(prefix="bench_train_")
    try:
        with observed(variables, record, step_fault=entry):
            try:
                train(tcfg, mcfg, ckpt_dir=out_dir + "/checkpoints",
                      log_dir=out_dir + "/runs", dataloader=loader)
                raise RuntimeError("train() returned before the window "
                                   "closed")
            except WindowClosed:
                pass
    finally:
        loader.close()
        tracer.read()
        import shutil
        shutil.rmtree(out_dir, ignore_errors=True)

    kernels, temporaries = {}, 0
    if on_chip:
        compiled = record.step.lower(*record.abstract_args).compile()
        from raft_tpu.ops.layout import KERNEL_NAMES
        kernels = census(compiled.as_text(), KERNEL_NAMES)
        temporaries = int(compiled.memory_analysis().temp_size_in_bytes)
        del compiled
    memory = dict(snapshots["memory"],
                  executable_temporaries_bytes=temporaries)

    steps = len(loader.fetch_s) - 1 - traffic["warmup_steps"]
    window_s = loader.window_close_s - loader.window_open_s
    samples_per_s = steps * traffic["batch_size"] / window_s
    in_window = {k: snapshots["window"][k] - snapshots["setup"][k]
                 for k in snapshots["setup"]}

    # the program's state goes before the reference comes
    ours = {"losses": record.losses,
            "first_grad": jax.tree.map(lambda m: np.asarray(m) / 0.1,
                                       adam_mu(record.opt_states[0])),
            "params": record.params[-1]}
    initial = record.initial_params
    record.step = record.abstract_args = None
    record.opt_states = record.params = None
    t0 = time.perf_counter()
    theirs = follow_reference(variables, pool[:traffic["followed_steps"]],
                              traffic, config)
    numbers = compare_steps(initial, ours, theirs)
    reference_s = time.perf_counter() - t0

    limits = cell["cell"]["limits"]
    compared = harness.Compared()
    for n, gap in enumerate(numbers["loss_gaps"]):
        compared.add(f"loss_gap_step{n + 1}", gap, limits["loss_gap"])
    if len(numbers["loss_gaps"]) < traffic["followed_steps"]:
        compared.add("steps_followed", float("nan"), 0)
    for name in ("grad_norm_gap_worst_leaf", "change_norm_gap_worst_leaf"):
        compared.add(name, numbers[name], limits[name])
    compared.add("compiles_in_window", in_window["compiles"], 0)
    expected = cell["cell"]["expected_kernels"] if on_chip else []
    compared.add("kernels_missing",
                 sum(1 for k in expected if not kernels.get(k)), 0)

    device = harness.device_facts(devices)
    device["memory_peak_bytes"] = memory["memory_peak_bytes"]
    window_waits = loader.wait_s[traffic["warmup_steps"]:]
    run_facts = {
        "steps": steps, "samples": steps * traffic["batch_size"],
        "window_s": window_s, "samples_per_s": samples_per_s,
        "loader_wait_s": float(sum(window_waits)),
        "step_s": np.diff(loader.fetch_s).tolist()[:200],
        "host_phase_s": loader.phases.seconds, "kernels": kernels,
        "setup_compile": snapshots["setup"], "window_compile": in_window,
        "followed": numbers, "reference_s": reference_s, "seed": seed,
        "memory": memory,
        "setup_phases_s": harness.durations(marks, process_start),
    }
    metrics, extra = harness.metrics_of(
        cell, tracer, run_facts, device, {"samples_per_s": samples_per_s,
                  "setup_s": snapshots["setup_s"]})
    result = {"correct": compared.correct,
              "attempted": steps * traffic["batch_size"], "failed": 0,
              "metrics": metrics, "device": device, **extra,
              "workload": cell["name"], "run": run_facts}
    return result, compared
