"""Traffic kind ``swa_train_steps``: ``raft_tpu.train.train()`` with the
sliding-window family (``model_family="afmoe"``), fed packed sequences
by the seeded loader that is also the benchmark's clock.

The window, the trace, the census and the comparison of the first steps
are ``drivers/ssm_train_steps.py::run_steps``, handed this module's
``configs_of``, ``make_batches`` (``lm_train_steps``' packing), seeded
weights and reference. This kind's own, around that call:

- the instruction names of its model stages and of its kernels' scopes
  (``stage_ops``), which the readers of device time sum over;
- the rows of ``correct`` that are its own: ``dropped`` and
  ``window_pairs_missed`` (the program's ``window_pairs`` counter summed
  over the window against the driver's own count, from ``segment_ids``,
  in the batches those steps trained on), in place of the state-space
  kind's ``resets_missed``; and the norm of the *difference* by leaf
  between the program's and the reference's first gradient and change
  (``grad_diff_worst_leaf``, ``change_diff_worst_leaf``: a gradient that
  turns without growing hides from the rows on each leaf's norm),
  compared where the cell's limits name them;
- the counts its metrics read (``swa_counts``, ``swa_traced_counts``).

``run_steps`` looks its ``stage_ops`` and ``compare_steps`` up in its
own module and ``census`` in ``dataset_pass`` as it runs, so ``run``
puts this kind's there for the length of the call, as
``train_steps.observed`` puts its seams into the program.
"""

from __future__ import annotations

import contextlib
import re

import numpy as np

from benchmark import harness
from benchmark.drivers import ssm_train_steps as steps
from benchmark.drivers.lm_train_steps import (compared_followed,  # noqa: F401
                                              make_batches, step_counters)
from benchmark.drivers.train_steps import compare_steps, leaf_norms

#: the model's and the step's ``jax.named_scope`` stages, then the
#: kernels' scopes (``ops/layout.py::KERNEL_NAMES``; the windowed one
#: first: the other's name begins it); an instruction belongs to the
#: last of them in its ``op_name``
STAGES = ("embed", "attention_window", "attention_full", "attn_gate",
          "dense_ffn", "moe_router", "moe_experts", "moe_shared", "lm_head",
          "token_loss", "grad_clip", "optimizer_update")
KERNEL_SCOPES = ("raft_attn_window", "raft_attn", "raft_expert_gmm")
_STAGE = re.compile(r"(?<![\w.])(" + "|".join(STAGES) + r")(?![\w.])")
_KERNEL = re.compile(r"(?<![\w.])(" + "|".join(KERNEL_SCOPES) + r")(?![\w.])")


# ------------------------------------------------------------------ weights

def seeded_variables(mcfg, seed: int):
    """The program's parameter tree filled from the seed, as host
    arrays: matrices ``normal / sqrt(fan_in)`` (the head's fan-in is the
    hidden size), the embedding ``normal / sqrt(hidden)`` (scaled by
    ``sqrt(hidden)`` in the model: of order 1), norm weights ``1 + 0.1
    normal``, the selection bias ``0.02 normal``: no term is multiplied
    by exactly 0 or 1."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.models.afmoe import Afmoe

    dummy = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(Afmoe(mcfg).init, jax.random.PRNGKey(0),
                            dummy, dummy, dummy)["params"]
    rng = np.random.default_rng([seed, 0x5A35])

    def make(path, leaf):
        name = path[-1].key
        normal = rng.standard_normal(leaf.shape, np.float32)
        if name.endswith("norm"):
            return 1.0 + 0.1 * normal
        if name == "expert_bias":
            return 0.02 * normal
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
                     operand_name: str = "identity", fault=None,
                     **departures):
    """The plain reference through the first steps: each step's loss,
    the first clipped gradient, and the parameters after the last.
    ``fault`` changes a batch on its way in; ``departures`` are the
    reference's own planted ones (``window=False``,
    ``positions_on_full=True``, ``keep_every=2``). Two programs a step,
    the moments on the host while the gradient is made (PERF.md,
    Findings of PR 33: as one program the step does not load beside its
    own state)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import afmoe as reference

    cfg = dict(config["reference"]["kwargs"])
    cfg["layer_types"] = tuple(cfg["layer_types"])
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
        batch = {k: batch[k] for k in ("tokens", "segment_ids",
                                       "positions")}
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


def difference_norms(a, b, less=None):
    """Per-leaf norms of ``a - b`` (of ``a - b - less``), in float64, a
    leaf at a time: whole float64 copies of trees of 705 M elements, a
    few at once, are more than the host has beside a run's own."""
    import jax

    def norm(*leaves):
        first, *rest = (np.asarray(x, np.float64) for x in leaves)
        for other in rest:
            first = first - other
        return float(np.linalg.norm(first))

    trees = (a, b) if less is None else (a, b, less)
    return np.array(jax.tree.leaves(jax.tree.map(norm, *trees)))


def compare_with_difference(initial, ours, theirs) -> dict:
    """``compare_steps``' numbers and, beside them, the norm of the
    difference by leaf: ``|ours - theirs|`` of the first gradient and of
    the parameters' change, each against the reference's norm of that
    leaf or of the median leaf, whichever is larger, over the leaves
    ``compare_steps`` keeps."""
    import jax

    numbers = compare_steps(initial, ours, theirs)
    ref_grad = leaf_norms(theirs["first_grad"])
    keep = ref_grad >= 1e-3 * np.median(ref_grad)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(theirs["first_grad"])[0]]
    for name, key, scale in (
            ("grad", "first_grad", ref_grad),
            ("change", "params",
             difference_norms(theirs["params"], initial))):
        gaps = np.where(keep, difference_norms(ours[key], theirs[key])
                        / np.maximum(scale, np.median(scale)), 0.0)
        numbers[f"{name}_diff_worst_leaf"] = float(gaps.max())
        numbers[f"{name}_diff_leaf"] = names[int(gaps.argmax())]
    return numbers


# --------------------------------------------------------------------- run

def configs_of(cell: dict, seed: int):
    """``TrainConfig`` and ``AfmoeConfig`` as ``train.py --model_family
    afmoe --lm_config <the configuration's file>`` builds them."""
    from raft_tpu.config import AfmoeConfig, TrainConfig

    traffic = cell["traffic"]
    model = dict(cell["config"]["model"])
    model["layer_types"] = tuple(model["layer_types"])
    tcfg = TrainConfig(
        name="benchmark", model_family="afmoe", lr=traffic["lr"],
        num_steps=traffic["num_steps"], batch_size=traffic["sequences"],
        seq_len=traffic["seq_len"], wdecay=traffic["wdecay"],
        epsilon=traffic["epsilon"], clip=traffic["clip"],
        val_freq=10 ** 9, seed=seed % (2 ** 31))
    return tcfg, AfmoeConfig(**model)


def allowed_pairs(batch: dict, window: int) -> dict:
    """The driver's own count, from ``segment_ids``, of the (query, key)
    pairs one layer's mask allows in a batch: a document of ``L`` tokens
    has ``L (L + 1) / 2`` on a full layer and, on a sliding one,
    ``W (W + 1) / 2 + (L - W) W`` once ``L`` passes the window ``W``."""
    window_pairs = causal_pairs = 0
    for row in np.asarray(batch["segment_ids"]):
        lengths = np.bincount(row).astype(np.int64)
        short = np.minimum(lengths, window)
        causal_pairs += int((lengths * (lengths + 1) // 2).sum())
        window_pairs += int((short * (short + 1) // 2
                             + (lengths - short) * window).sum())
    return {"window_pairs": window_pairs, "causal_pairs": causal_pairs}


def stage_ops(compiled_text: str) -> dict:
    """``{stage or kernel scope: [HLO instruction names]}`` of a
    compiled step: every instruction under a stage's
    ``jax.named_scope`` (forward, recomputed and backward, by its
    ``op_name``) and, apart, every instruction under a kernel's scope."""
    from benchmark.tools.scope_summary import op_names
    from raft_tpu.ops.layout import hlo_instructions
    out: dict = {}
    whole = "\n".join(hlo_instructions(compiled_text))
    for instruction, op_name in op_names(whole).items():
        for pattern in (_STAGE, _KERNEL):
            found = pattern.findall(op_name)
            if found:
                out.setdefault(found[-1], []).append(instruction)
    return out


@contextlib.contextmanager
def _this_kinds():
    """Inside, ``run_steps`` maps instructions to this kind's stages,
    compares with this kind's numbers, and counts kernels an
    instruction at a time (``dataset_pass.census`` reads a line at a
    time, and the windowed kernel's instructions are printed over
    several: PERF.md section 7)."""
    from benchmark.drivers import dataset_pass
    from raft_tpu.ops.layout import kernel_census
    theirs = steps.stage_ops, steps.compare_steps, dataset_pass.census
    steps.stage_ops, steps.compare_steps = stage_ops, compare_with_difference
    dataset_pass.census = kernel_census
    try:
        yield
    finally:
        steps.stage_ops, steps.compare_steps, dataset_pass.census = theirs


def run(cell: dict, devices, *, seed: int, seconds: float, trace: bool,
        process_start: float, entry=None):
    """``entry`` (tests of ``correct`` only) is a fault to plant under
    the jitted step."""
    traffic, limits = cell["traffic"], cell["cell"]["limits"]
    window = cell["config"]["model"]["sliding_window"]
    pools = []

    def batches(seed, traffic, vocab):
        pools.append(make_batches(seed, traffic, vocab))
        return pools[-1]

    with _this_kinds():
        result, theirs = steps.run_steps(
            cell, devices, seed=seed, seconds=seconds, trace=trace,
            process_start=process_start, entry=entry, configs_of=configs_of,
            make_batches=batches, seeded_variables=seeded_variables,
            follow_reference=follow_reference)

    # what the program counted, step by step (step n, counted from 1,
    # trained on pool[(n - 1) % pool]), beside the driver's own count
    run_facts = result["run"]
    warm, n_steps = traffic["warmup_steps"], run_facts["steps"]
    counters = step_counters(warm + 1, warm + n_steps)
    allowed = [allowed_pairs(b, window) for b in pools[0]]

    def counts(units):
        units = [u for u in units if u in counters]
        of = lambda key: sum(int(counters[u].get(key, 0))  # noqa: E731
                             for u in units)
        ours = lambda key: sum(                            # noqa: E731
            allowed[(u - 1) % len(allowed)][key] for u in units)
        return {"steps": len(units),
                "tokens": traffic["sequences"] * traffic["seq_len"]
                * len(units),
                "loss_tokens": of("tokens"), "routed_here": of("routed_here"),
                "dropped": of("dropped"), "window_pairs": of("window_pairs"),
                "causal_pairs": of("causal_pairs"),
                "window_pairs_by_driver": ours("window_pairs"),
                "causal_pairs_by_driver": ours("causal_pairs")}

    last = warm + n_steps
    window_counts = counts(range(warm + 1, last + 1))
    traced = (run_facts.get("ssm_traced_counts") or {}).get("steps", 0)
    run_facts["swa_counts"] = window_counts
    run_facts["swa_traced_counts"] = counts(
        range(last - traced + 1, last + 1)) if traced else None
    for key in ("ssm_counts", "ssm_traced_counts"):
        run_facts.pop(key, None)

    # the rows run_steps compared, less the state-space kind's own, and
    # then this kind's
    whole = window_counts["steps"] == n_steps
    compared = harness.Compared()
    for row in theirs.rows:
        if row["name"] != "resets_missed":
            compared.add(row["name"], row["value"], row["limit"])
    for name in ("grad_diff_worst_leaf", "change_diff_worst_leaf"):
        if name in limits:
            compared.add(name, run_facts["followed"].get(name), limits[name])
    compared.add("dropped", window_counts["dropped"] if whole
                 else float("nan"), 0)
    compared.add("window_pairs_missed", abs(
        window_counts["window_pairs"]
        - window_counts["window_pairs_by_driver"]) if whole
        else float("nan"), 0)
    result["correct"] = compared.correct
    return result, compared
