"""The step's family table (``parallel/train_step.py::FAMILIES``) leaves
the flow families alone: for ``raft`` and ``sparse`` one step on one
seed gives the loss, the gradient norms by leaf and the state after the
step that the parent commit gave (PR 28's tree, computed once on this
CPU and kept in ``train_step_parent_pr28.json``), to 1e-6 relative, and
the compiled step has as many instructions as the parent's."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.config import (FLOW_FAMILIES, MODEL_FAMILIES, TOKEN_FAMILIES,
                             OursConfig, RAFTConfig, TrainConfig)
from raft_tpu.parallel import create_train_state, make_train_step
from raft_tpu.parallel.train_step import FAMILIES, family_of

with open(os.path.join(os.path.dirname(__file__),
                       "train_step_parent_pr28.json")) as f:
    PARENT = json.load(f)


def _batch(B, H, W):
    rng = np.random.default_rng(7)
    return {"image1": jnp.asarray(rng.uniform(0, 255, (B, H, W, 3)),
                                  jnp.float32),
            "image2": jnp.asarray(rng.uniform(0, 255, (B, H, W, 3)),
                                  jnp.float32),
            "flow": jnp.asarray(rng.normal(size=(B, H, W, 2)) * 2,
                                jnp.float32),
            "valid": jnp.asarray(rng.uniform(size=(B, H, W)) > 0.1,
                                 jnp.float32)}


def _setup(family):
    if family == "raft":
        from raft_tpu.models.raft import RAFT
        hw = (64, 64)
        tcfg = TrainConfig(batch_size=2, image_size=hw, num_steps=50,
                           iters=2, lr=1e-4)
        return tcfg, RAFT(RAFTConfig(small=True, iters=2)), hw
    from raft_tpu.models import SparseRAFT
    hw = (32, 48)
    tcfg = TrainConfig(batch_size=2, image_size=hw, num_steps=10, iters=2,
                       model_family="sparse", sparse_lambda=0.1, lr=1e-4)
    return tcfg, SparseRAFT(OursConfig(
        base_channel=16, d_model=32, num_feature_levels=2,
        outer_iterations=1, num_keypoints=4, n_heads=4, n_points=2,
        dropout=0.0)), hw


def _norms(tree):
    return [float(np.linalg.norm(np.asarray(x, np.float64)))
            for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("family", ["raft", "sparse"])
def test_refactored_step_gives_the_parents_numbers(family):
    from benchmark.drivers.train_steps import adam_mu
    tcfg, model, hw = _setup(family)
    # initialised under jit (the parent's numbers were too): eagerly the
    # sparse family's init alone takes a minute of this CPU
    state = jax.jit(lambda: create_train_state(
        jax.random.PRNGKey(0), model, tcfg, hw))()
    step = make_train_step(tcfg, donate=False)
    batch, key = _batch(2, *hw), jax.random.PRNGKey(1)
    # compiled once: the text for the instruction count, the executable
    # for the numbers
    compiled = step.lower(state, batch, key).compile()
    new, metrics = compiled(state, batch, key)
    parent = PARENT[family]
    ours = {
        "loss": float(metrics["loss"]),
        "grad_norm": float(metrics["grad_norm"]),
        # Adam's first moment after one step is a tenth of the clipped
        # gradient: the gradient by leaf, as the optimizer got it
        "grad_leaf_norms": _norms(jax.tree.map(
            lambda m: np.asarray(m) / 0.1, adam_mu(new.opt_state))),
        "param_leaf_norms": _norms(new.params),
        "batch_stats_leaf_norms": _norms(new.batch_stats),
    }
    for name, value in ours.items():
        np.testing.assert_allclose(value, parent[name], rtol=1e-6,
                                   err_msg=name)
    assert sum(1 for line in compiled.as_text().splitlines()
               if " = " in line) == parent["hlo_instructions"]


def test_every_family_has_a_row_and_only_those():
    assert set(FAMILIES) == set(MODEL_FAMILIES)
    assert set(FLOW_FAMILIES) | set(TOKEN_FAMILIES) == set(MODEL_FAMILIES)
    assert "lfm2_moe" in TOKEN_FAMILIES and "raft" in FLOW_FAMILIES
    with pytest.raises(ValueError, match="unknown model_family"):
        family_of(TrainConfig(model_family="nope"))


def test_decay_mask_leaves_the_selection_bias_alone():
    from raft_tpu.optim import _decay_mask
    mask = _decay_mask({"layers_1": {"feed_forward": {
        "expert_bias": jnp.zeros(8), "router": jnp.zeros((4, 8))}},
        "bn": {"running_mean": jnp.zeros(2), "running_var": jnp.ones(2),
               "scale": jnp.ones(2)},
        "conv": {"kernel": jnp.zeros((1, 1, 2, 2))}})
    assert mask == {"layers_1": {"feed_forward": {"expert_bias": False,
                                                  "router": True}},
                    "bn": {"running_mean": False, "running_var": False,
                           "scale": False},
                    "conv": {"kernel": True}}
