"""Traffic kind ``lm_train_steps``: ``raft_tpu.train.train()`` with the
token family (``model_family="lfm2_moe"``), fed packed sequences by the
seeded loader that is also the benchmark's clock.

The loader, the two seams (seeded weights in place of the program's
initialisation, host copies of the state after each followed step) and
the comparison of the first steps are ``drivers/train_steps.py``'s; what
is this kind's own: the configurations, the batches (documents of
log-normal length packed without padding), the seeded weights (made on
the host: the device has no room for a second copy of them), the plain
reference followed a step at a time, and the counts a run reports for
the token family's metrics (``lm_counts``: tokens, causal pairs, rows
routed to held experts, all over the window's steps; ``dropped`` is
compared with 0).
"""

from __future__ import annotations

import tempfile
import time

import numpy as np

from benchmark import harness, lm_flops
from benchmark.drivers.train_steps import (ClockLoader, Observed,
                                           WindowClosed, adam_mu,
                                           compare_steps, observed)

ROOT_SPAN = "train.step"


# ------------------------------------------------------------------ traffic

def make_batches(seed: int, traffic: dict, vocab: int):
    """``pool`` batches of ``sequences`` x ``seq_len``: each sequence
    packed from documents whose lengths are log-normal (``doc_median``,
    ``doc_sigma``), cut at ``seq_len``, the last truncated; ids uniform
    over the vocabulary held."""
    rng = np.random.default_rng([seed, 0x70C5])
    b, s = traffic["sequences"], traffic["seq_len"]
    out = []
    for _ in range(traffic["pool"]):
        segment_ids = np.empty((b, s), np.int32)
        positions = np.empty((b, s), np.int32)
        for row in range(b):
            at = doc = 0
            while at < s:
                length = int(np.clip(rng.lognormal(
                    np.log(traffic["doc_median"]), traffic["doc_sigma"]),
                    1, s))
                length = min(length, s - at)
                segment_ids[row, at:at + length] = doc
                positions[row, at:at + length] = np.arange(length)
                at, doc = at + length, doc + 1
        out.append({"tokens": rng.integers(0, vocab, (b, s),
                                           dtype=np.int32),
                    "segment_ids": segment_ids, "positions": positions})
    return out


# ------------------------------------------------------------------ weights

def seeded_variables(mcfg, seed: int):
    """The program's parameter tree filled from the seed, as host
    arrays: matrices ``normal / sqrt(fan_in)``, the embedding ``normal /
    sqrt(hidden)`` (logits of order 1), norm weights ``1 + 0.1 normal``,
    convolution taps ``0.5 normal``, the selection bias ``0.02
    normal``: no term is multiplied by exactly 0 or 1."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.models.lfm2 import LFM2

    dummy = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(LFM2(mcfg).init, jax.random.PRNGKey(0),
                            dummy, dummy, dummy)["params"]
    rng = np.random.default_rng([seed, 0x1F32])

    def make(path, leaf):
        name = path[-1].key
        normal = rng.standard_normal(leaf.shape, np.float32)
        if name.endswith("norm"):
            return 1.0 + 0.1 * normal
        if name == "expert_bias":
            return 0.02 * normal
        if name == "conv":
            return 0.5 * normal
        if name == "embed_tokens":
            return normal * np.float32(leaf.shape[-1] ** -0.5)
        if len(leaf.shape) >= 2:
            return normal * np.float32(leaf.shape[-2] ** -0.5)
        raise ValueError(f"no rule for leaf {jax.tree_util.keystr(path)}")

    params = jax.tree_util.tree_map_with_path(make, shapes)
    # the state of a model without batch statistics holds an empty
    # FrozenDict there; the seam maps over both trees
    from flax.core import FrozenDict
    return {"params": params, "batch_stats": FrozenDict({})}


# --------------------------------------------------------------- reference

def follow_reference(variables, batches, traffic, config,
                     operand_name: str = "identity", fault=None):
    """The plain reference through the first steps: each step's loss,
    the first clipped gradient, and the parameters after the last."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import lfm2 as reference

    cfg = config["reference"]["kwargs"]

    def step_fn(params, opt, batch, n):
        return reference.train_step(
            params, opt, batch, n, cfg=cfg, lr=traffic["lr"],
            total_steps=traffic["num_steps"] + 100,
            wdecay=traffic["wdecay"], eps=traffic["epsilon"],
            clip=traffic["clip"],
            operand=getattr(reference, operand_name))

    step = jax.jit(step_fn, donate_argnums=(0, 1))
    params = jax.tree.map(jnp.array, variables["params"])
    opt = {"mu": jax.tree.map(jnp.zeros_like, params),
           "nu": jax.tree.map(jnp.zeros_like, params)}
    losses, first_grad = [], None
    for n, batch in enumerate(batches):
        if fault is not None:
            batch = fault(batch)
        before = jax.device_get(params) if getattr(
            fault, "after", None) else None
        with jax.default_matmul_precision("highest"):
            params, opt, loss, grads = step(params, opt, batch, n)
        if before is not None:
            params = jax.tree.map(jnp.array, fault.after(
                before, jax.device_get(params)))
        losses.append(float(loss))
        if n == 0:
            first_grad = jax.device_get(grads)
        del grads
    return {"losses": losses, "first_grad": first_grad,
            "params": jax.device_get(params)}


def compared_followed(numbers: dict, limits: dict,
                      followed_steps: int) -> harness.Compared:
    """The rows of ``correct`` that come from following the first
    steps (``compare_steps``' numbers), each beside the cell's limit:
    what a run compares, and what ``tools/steps_control.py`` holds the
    control and the planted faults to."""
    compared = harness.Compared()
    for n, gap in enumerate(numbers["loss_gaps"]):
        compared.add(f"loss_gap_step{n + 1}", gap, limits["loss_gap"])
    if len(numbers["loss_gaps"]) < followed_steps:
        compared.add("steps_followed", float("nan"), 0)
    for name in ("grad_norm_gap_worst_leaf", "change_norm_gap_worst_leaf"):
        compared.add(name, numbers[name], limits[name])
    return compared


# --------------------------------------------------------------------- run

def configs_of(cell: dict, seed: int):
    """``TrainConfig`` and ``LMConfig`` as ``train.py --model_family
    lfm2_moe --lm_config <the configuration's file>`` builds them."""
    from raft_tpu.config import LMConfig, TrainConfig

    traffic = cell["traffic"]
    model = dict(cell["config"]["model"])
    model["layer_types"] = tuple(model["layer_types"])
    tcfg = TrainConfig(
        name="benchmark", model_family="lfm2_moe", lr=traffic["lr"],
        num_steps=traffic["num_steps"], batch_size=traffic["sequences"],
        seq_len=traffic["seq_len"], wdecay=traffic["wdecay"],
        epsilon=traffic["epsilon"], clip=traffic["clip"],
        val_freq=10 ** 9, seed=seed % (2 ** 31))
    return tcfg, LMConfig(**model)


def step_counters(first_unit: int, last_unit: int):
    """The program's counters on its ``train.step`` spans, for the steps
    numbered ``first_unit..last_unit``: ``{unit: args}``."""
    from raft_tpu.utils.profiling import host_timer
    return {s.unit: s.args for s in host_timer().spans()
            if s.name == ROOT_SPAN and s.args.get("complete")
            and first_unit <= s.unit <= last_unit}


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
    marks.append(("model_and_weights", time.perf_counter()))
    pool = make_batches(seed, traffic, mcfg.vocab)
    pairs = [lm_flops.causal_pairs(b["segment_ids"]) for b in pool]
    marks.append(("batch_pool", time.perf_counter()))

    tracer = harness.Trace(trace)
    snapshots = {}

    def window_opens():
        snapshots["setup"] = counter.snapshot()
        snapshots["setup_s"] = time.perf_counter() - process_start

    def step_done(since_open):
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
    out_dir = tempfile.mkdtemp(prefix="bench_lm_")
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

    warm = traffic["warmup_steps"]
    steps = len(loader.fetch_s) - 1 - warm
    window_s = loader.window_close_s - loader.window_open_s
    samples_per_s = steps * traffic["sequences"] / window_s
    in_window = {k: snapshots["window"][k] - snapshots["setup"][k]
                 for k in snapshots["setup"]}

    # what the program counted, step by step (step n, counted from 1,
    # trained on pool[(n - 1) % pool])
    counters = step_counters(warm + 1, warm + steps)
    tokens_in = traffic["sequences"] * traffic["seq_len"]

    def counts(units):
        units = [u for u in units if u in counters]
        return {"steps": len(units),
                "tokens": tokens_in * len(units),
                "buffer_rows": tokens_in * mcfg.num_experts_per_tok
                * lm_flops.expert_layers(config["model"]) * len(units),
                "causal_pairs": sum(pairs[(u - 1) % len(pool)]
                                    for u in units),
                "routed_here": sum(int(counters[u].get("routed_here", 0))
                                   for u in units),
                "dropped": sum(int(counters[u].get("dropped", 0))
                               for u in units)}

    window_counts = counts(range(warm + 1, warm + steps + 1))
    traced_counts = counts(range(
        snapshots.get("traced_from_step", warm + steps + 1),
        warm + steps + 1)) if trace else None

    # the program's state goes before the reference comes
    ours = {"losses": record.losses,
            "first_grad": jax.tree.map(lambda m: np.asarray(m) / 0.1,
                                       adam_mu(record.opt_states[0])),
            "params": record.params[-1]}
    initial = record.initial_params
    record.step = record.abstract_args = None
    record.opt_states = record.params = None
    jax.clear_caches()
    t0 = time.perf_counter()
    theirs = follow_reference(variables, pool[:traffic["followed_steps"]],
                              traffic, config)
    numbers = compare_steps(initial, ours, theirs)
    reference_s = time.perf_counter() - t0

    compared = compared_followed(numbers, cell["cell"]["limits"],
                                 traffic["followed_steps"])
    compared.add("dropped", window_counts["dropped"]
                 if window_counts["steps"] == steps else float("nan"), 0)
    compared.add("compiles_in_window", in_window["compiles"], 0)
    expected = cell["cell"]["expected_kernels"] if on_chip else []
    compared.add("kernels_missing",
                 sum(1 for k in expected if not kernels.get(k)), 0)

    device = harness.device_facts(devices)
    device["memory_peak_bytes"] = memory["memory_peak_bytes"]
    window_waits = loader.wait_s[warm:]
    run_facts = {
        "steps": steps, "samples": steps * traffic["sequences"],
        "window_s": window_s, "samples_per_s": samples_per_s,
        "tokens_per_s": steps * tokens_in / window_s,
        "loader_wait_s": float(sum(window_waits)),
        "step_s": np.diff(loader.fetch_s).tolist()[:200],
        "host_phase_s": loader.phases.seconds, "kernels": kernels,
        "setup_compile": snapshots["setup"], "window_compile": in_window,
        "lm_counts": window_counts, "lm_traced_counts": traced_counts,
        "followed": numbers, "reference_s": reference_s, "seed": seed,
        "memory": memory,
        "setup_phases_s": harness.durations(marks, process_start),
    }
    metrics, extra = harness.metrics_of(
        cell, tracer, run_facts, device, {"samples_per_s": samples_per_s,
                  "setup_s": snapshots["setup_s"]})
    result = {"correct": compared.correct,
              "attempted": steps * traffic["sequences"], "failed": 0,
              "metrics": metrics, "device": device, **extra,
              "workload": cell["name"], "run": run_facts}
    return result, compared
