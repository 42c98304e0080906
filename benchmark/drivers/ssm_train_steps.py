"""Traffic kind ``ssm_train_steps``: ``raft_tpu.train.train()`` with the
state-space hybrid (``model_family="granitemoehybrid"``), fed packed
sequences by the seeded loader that is also the benchmark's clock.

The loader, the two seams and the comparison of the first steps are
``drivers/train_steps.py``'s; the batches, the rows that come from
following the first steps and the reading of the program's counters are
``drivers/lm_train_steps.py``'s. This kind's own: its configurations,
its seeded weights (made on the host), the plain reference followed a
step at a time, the document starts it counts itself for
``resets_missed``, and the instruction names of each model stage
(``stage_ops``) that the readers of device time by stage sum over.

The window, the trace, the census and the comparison are one function,
``run_steps``, that takes ``configs_of``, ``make_batches``,
``seeded_variables`` and ``follow_reference`` as arguments; ``run`` hands
it this module's four (PERF.md section 7 (b): the seam the two older
kinds' copies can be folded onto).
"""

from __future__ import annotations

import re
import tempfile
import time

import numpy as np

from benchmark import harness, lm_flops
from benchmark.drivers.lm_train_steps import (compared_followed,  # noqa: F401
                                              make_batches, step_counters)
from benchmark.drivers.train_steps import (ClockLoader, Observed,  # noqa: F401
                                           WindowClosed, adam_mu,
                                           compare_steps, observed)

#: the model's and the step's ``jax.named_scope`` stages; an instruction
#: belongs to the last of them in its ``op_name``
STAGES = ("embed", "ssm_in_proj", "ssm_conv", "ssd_scan", "ssm_gated_norm",
          "ssm_out_proj", "attention", "dense_ffn", "lm_head", "token_loss",
          "grad_clip", "optimizer_update")
_STAGE = re.compile(r"(?<![\w.])(" + "|".join(STAGES) + r")(?![\w.])")


# ------------------------------------------------------------------ weights

def seeded_variables(mcfg, seed: int):
    """The program's parameter tree filled from the seed, as host
    arrays, in the published initialiser's ranges with no term
    multiplied by exactly 0 or 1: matrices ``normal / sqrt(fan_in)``,
    the embedding ``normal / sqrt(hidden)``, norm weights and ``D``
    ``1 + 0.1 normal``, convolution taps ``0.5 normal`` and bias ``0.1
    normal``, ``A_log`` the log of uniform(1, 16), ``dt_bias`` the
    inverse softplus of log-uniform(0.001, 0.1)."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.models.granitemoehybrid import GraniteMoeHybrid

    dummy = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(GraniteMoeHybrid(mcfg).init,
                            jax.random.PRNGKey(0), dummy, dummy,
                            dummy)["params"]
    rng = np.random.default_rng([seed, 0x55D2])

    def make(path, leaf):
        name = path[-1].key
        if name == "A_log":
            return np.log(rng.uniform(1.0, 16.0, leaf.shape)).astype(
                np.float32)
        if name == "dt_bias":
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), leaf.shape))
            return (dt + np.log(-np.expm1(-dt))).astype(np.float32)
        normal = rng.standard_normal(leaf.shape, np.float32)
        if name.endswith("norm") or name == "D":
            return 1.0 + 0.1 * normal
        if name == "conv":
            return 0.5 * normal
        if name == "conv_bias":
            return 0.1 * normal
        if name == "embed_tokens":
            return normal * np.float32(leaf.shape[-1] ** -0.5)
        if len(leaf.shape) == 2:
            return normal * np.float32(leaf.shape[0] ** -0.5)
        raise ValueError(f"no rule for leaf {jax.tree_util.keystr(path)}")

    params = jax.tree_util.tree_map_with_path(make, shapes)
    # the state of a model without batch statistics holds an empty
    # FrozenDict there; the seam maps over both trees
    from flax.core import FrozenDict
    return {"params": params, "batch_stats": FrozenDict({})}


# --------------------------------------------------------------- reference

def follow_reference(variables, batches, traffic, config,
                     operand_name: str = "identity", fault=None,
                     **departures):
    """The plain reference through the first steps: each step's loss,
    the first clipped gradient, and the parameters after the last.
    ``fault`` changes a batch on its way in; ``departures`` are the
    reference's own planted ones (``resets=False``, ``keep_every=2``)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import granitemoehybrid as reference

    cfg = config["reference"]["kwargs"]
    # two programs a step, and the moments wait on the host while the
    # gradient is made: that program holds the parameters, the
    # gradient's accumulator, the gradient's output and 2.6 GiB of
    # activations (11.3 GiB for a described v5e); the moments' 5.8 GiB
    # beside them do not load
    grads_of = jax.jit(lambda params, batch: reference.loss_and_grads(
        params, batch, cfg, getattr(reference, operand_name),
        **departures))
    update = jax.jit(
        lambda params, opt, grads, n: reference.apply_update(
            params, opt, grads, n, lr=traffic["lr"],
            total_steps=traffic["num_steps"] + 100,
            wdecay=traffic["wdecay"], eps=traffic["epsilon"],
            clip=traffic["clip"]), donate_argnums=(0, 1, 2))
    params = jax.tree.map(jnp.array, variables["params"])
    opt_on_host = None
    losses, first_grad = [], None
    for n, batch in enumerate(batches):
        if fault is not None:
            batch = fault(batch)
        batch = {k: batch[k] for k in ("tokens", "segment_ids")}
        with jax.default_matmul_precision("highest"):
            loss, grads = grads_of(params, batch)
            losses.append(float(loss))
            opt = {"mu": jax.tree.map(jnp.zeros_like, params),
                   "nu": jax.tree.map(jnp.zeros_like, params)} \
                if opt_on_host is None else jax.device_put(opt_on_host)
            params, opt, grads = update(params, opt, grads, n)
        if n == 0:
            first_grad = jax.device_get(grads)
        if n + 1 < len(batches):
            opt_on_host = jax.device_get(opt)
        del grads, opt
    return {"losses": losses, "first_grad": first_grad,
            "params": jax.device_get(params)}


# --------------------------------------------------------------------- run

def configs_of(cell: dict, seed: int):
    """``TrainConfig`` and ``GraniteHybridConfig`` as ``train.py
    --model_family granitemoehybrid --lm_config <the configuration's
    file>`` builds them."""
    from raft_tpu.config import GraniteHybridConfig, TrainConfig

    traffic = cell["traffic"]
    model = dict(cell["config"]["model"])
    model["layer_types"] = tuple(model["layer_types"])
    tcfg = TrainConfig(
        name="benchmark", model_family="granitemoehybrid", lr=traffic["lr"],
        num_steps=traffic["num_steps"], batch_size=traffic["sequences"],
        seq_len=traffic["seq_len"], wdecay=traffic["wdecay"],
        epsilon=traffic["epsilon"], clip=traffic["clip"],
        val_freq=10 ** 9, seed=seed % (2 ** 31))
    return tcfg, GraniteHybridConfig(**model)


def document_starts(batch: dict) -> int:
    """The driver's own count of what ``ssm_resets`` counts: tokens
    whose document is not the one of the token before them."""
    return int((np.diff(batch["segment_ids"], axis=1) != 0).sum())


def stage_ops(compiled_text: str) -> dict:
    """``{stage: [HLO instruction names]}`` of a compiled step: every
    instruction under the stage's ``jax.named_scope``, forward,
    recomputed and backward, by its ``op_name``."""
    from benchmark.tools.scope_summary import op_names
    out: dict = {}
    for instruction, op_name in op_names(compiled_text).items():
        found = _STAGE.findall(op_name)
        if found:
            out.setdefault(found[-1], []).append(instruction)
    return out


class _First(list):
    """Keeps what was appended first (the followed steps' optimizer
    states are 6 GB each on the host; the comparison reads the first)."""

    def append(self, item):
        if not self:
            super().append(item)


class _Last(list):
    """Keeps what was appended last (the comparison reads the
    parameters after the last followed step)."""

    def append(self, item):
        self[:] = [item]


def run_steps(cell: dict, devices, *, seed: int, seconds: float,
              trace: bool, process_start: float, entry=None, configs_of,
              make_batches, seeded_variables, follow_reference):
    """Set up, run ``train()`` through warm-up and window, then follow
    the first steps with the reference."""
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
    starts = [document_starts(b) for b in pool]
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
    record.opt_states, record.params = _First(), _Last()
    out_dir = tempfile.mkdtemp(prefix="bench_ssm_")
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

    kernels, stages, temporaries = {}, {}, 0
    if on_chip:
        compiled = record.step.lower(*record.abstract_args).compile()
        from raft_tpu.ops.layout import KERNEL_NAMES
        text = compiled.as_text()
        kernels = census(text, KERNEL_NAMES)
        if trace:
            stages = stage_ops(text)
        temporaries = int(compiled.memory_analysis().temp_size_in_bytes)
        del compiled, text
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
        of = lambda key: sum(int(counters[u].get(key, 0))  # noqa: E731
                             for u in units)
        batch_of = lambda seen: sum(seen[(u - 1) % len(pool)]  # noqa: E731
                                    for u in units)
        return {"steps": len(units), "tokens": tokens_in * len(units),
                "loss_tokens": of("tokens"), "chunks": of("ssd_chunks"),
                "ssm_resets": of("ssm_resets"),
                "document_starts": batch_of(starts),
                "causal_pairs": batch_of(pairs)}

    window_counts = counts(range(warm + 1, warm + steps + 1))
    traced_counts = counts(range(
        snapshots.get("traced_from_step", warm + steps + 1),
        warm + steps + 1)) if trace else None

    # the program's state goes before the reference comes
    ours = {"losses": record.losses,
            "first_grad": jax.tree.map(lambda m: np.asarray(m) / 0.1,
                                       adam_mu(record.opt_states[0])),
            "params": record.params[-1]}
    record.step = record.abstract_args = record.initial_params = None
    record.opt_states = record.params = None
    jax.clear_caches()
    t0 = time.perf_counter()
    theirs = follow_reference(variables, pool[:traffic["followed_steps"]],
                              traffic, config)
    # the seeded weights are what the program's state began as
    numbers = compare_steps(variables["params"], ours, theirs)
    reference_s = time.perf_counter() - t0

    compared = compared_followed(numbers, cell["cell"]["limits"],
                                 traffic["followed_steps"])
    compared.add("resets_missed",
                 abs(window_counts["document_starts"]
                     - window_counts["ssm_resets"])
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
        "ssm_counts": window_counts, "ssm_traced_counts": traced_counts,
        "stage_ops": stages,
        "followed": numbers, "reference_s": reference_s, "seed": seed,
        "memory": memory,
        "setup_phases_s": harness.durations(marks, process_start),
    }
    metrics, extra = harness.metrics_of(
        cell, tracer, run_facts, device, {"samples_per_s": samples_per_s,
                  "setup_s": snapshots["setup_s"]})
    # the instruction lists are the readers'; the line gets each stage's
    # device seconds in the traced window instead
    if tracer.reduced is not None:
        ops = tracer.reduced["ops"]
        run_facts["stage_s"] = {
            stage: sum(ops.get(name, 0.0) for name in names)
            for stage, names in stages.items()}
    run_facts["stage_ops"] = {k: len(v) for k, v in stages.items()}
    result = {"correct": compared.correct,
              "attempted": steps * traffic["sequences"], "failed": 0,
              "metrics": metrics, "device": device, **extra,
              "workload": cell["name"], "run": run_facts}
    return result, compared


def run(cell: dict, devices, *, seed: int, seconds: float, trace: bool,
        process_start: float, entry=None):
    """``entry`` (tests of ``correct`` only) is a fault to plant under
    the jitted step."""
    return run_steps(cell, devices, seed=seed, seconds=seconds, trace=trace,
                     process_start=process_start, entry=entry,
                     configs_of=configs_of, make_batches=make_batches,
                     seeded_variables=seeded_variables,
                     follow_reference=follow_reference)
