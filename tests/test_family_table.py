"""The family table (``raft_tpu/families.py``): one row a
``model_family``, asked by every entry point and compared by none.

Moving the step's loss makers there leaves the flow families alone: for
``raft`` and ``sparse`` one step on one seed gives the loss, the
gradient norms by leaf and the state after the step that the parent
commit gave (PR 28's tree, computed once on this CPU and kept in
``train_step_parent_pr28.json``), to 1e-6 relative, and the compiled
step has as many instructions as the parent's."""

import ast
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.config import (AfmoeConfig, GraniteHybridConfig, LMConfig,
                             OursConfig, RAFTConfig, TrainConfig)
from raft_tpu.families import FAMILIES, FLOW_FAMILIES, family_of
from raft_tpu.parallel import create_train_state, make_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the four snapshot families PR 31 removed
REMOVED = ("keypoint_transformer", "dual_query", "two_stage",
           "full_transformer")

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


def test_the_family_tuples_are_computed_from_the_table():
    import raft_tpu.config as config
    assert FLOW_FAMILIES == tuple(
        name for name, row in FAMILIES.items() if not row.tokens)
    assert set(FAMILIES) == {"raft", "sparse", "lfm2_moe",
                             "granitemoehybrid", "afmoe"}
    for gone in ("MODEL_FAMILIES", "TOKEN_FAMILIES", "FLOW_FAMILIES"):
        assert not hasattr(config, gone)
    with pytest.raises(ValueError, match="unknown model_family"):
        family_of("nope")


@pytest.mark.parametrize("name", REMOVED)
def test_a_removed_family_is_refused_with_the_rows_listed(name):
    with pytest.raises(ValueError) as err:
        family_of(name)
    assert str(sorted(FAMILIES)) in str(err.value)
    with pytest.raises(ValueError, match="unknown model_family"):
        make_train_step(TrainConfig(model_family=name))


_TINY_LM = LMConfig(
    hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
    num_hidden_layers=3, layer_types=("conv", "full_attention", "conv"),
    num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
    num_experts=8, num_experts_per_tok=2, vocab_size=256, experts_held=2,
    expert_offset=2, vocab_held=64, mixed_precision=False)


_TINY_SSM = GraniteHybridConfig(
    hidden_size=64, shared_intermediate_size=96, num_hidden_layers=2,
    layer_types=("mamba", "attention"), num_attention_heads=4,
    num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16,
    mamba_d_state=16, mamba_chunk_size=16, vocab_size=256, vocab_held=64)


_TINY_SWA = AfmoeConfig(
    hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
    num_hidden_layers=2, layer_types=("sliding_attention",
                                      "full_attention"),
    num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, sliding_window=8, num_experts=8, num_experts_per_tok=2,
    vocab_size=256, experts_held=2, expert_offset=2, vocab_held=64)


@pytest.mark.parametrize("name,mcfg,expects", [
    ("raft", RAFTConfig(small=True), "fnet"),
    ("sparse", RAFTConfig(), "query_embed"),
    ("lfm2_moe", _TINY_LM, "embed_tokens"),
    ("granitemoehybrid", _TINY_SSM, "embed_tokens"),
    ("afmoe", _TINY_SWA, "lm_head"),
])
def test_a_row_builds_its_model_and_its_init_inputs_fit(name, mcfg,
                                                        expects):
    """``build`` and ``init_inputs`` of one row belong together: the
    model's ``init`` traces on what the row says it is initialised on
    (shapes only: nothing is compiled)."""
    row = family_of(name)
    tcfg = TrainConfig(model_family=name, image_size=(64, 64), seq_len=16)
    model = row.build(mcfg)
    args, kwargs = row.init_inputs(tcfg, None)
    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(
        lambda: model.init({"params": key, "dropout": key}, *args,
                           **kwargs))
    assert expects in shapes["params"]
    assert args[0].ndim == (2 if row.tokens else 4)
    # a token row names its config dataclass and the counters its step
    # reports; an image row has neither
    assert (row.config_cls is type(mcfg)) == row.tokens
    assert bool(row.step_counters) == row.tokens


def test_the_steps_counters_are_the_rows():
    """``train()`` puts on a step's span what the family's row lists;
    no module holds one tuple for every token family."""
    import raft_tpu.train as train
    assert not hasattr(train, "STEP_COUNTERS")
    assert family_of("lfm2_moe").step_counters == (
        "tokens", "routed_here", "expert_load_max", "dropped")
    assert family_of("granitemoehybrid").step_counters == (
        "tokens", "ssm_resets", "ssd_chunks")
    assert family_of("afmoe").step_counters == (
        "tokens", "routed_here", "expert_load_max", "dropped",
        "window_pairs", "causal_pairs")
    assert family_of("raft").step_counters == ()


@pytest.mark.parametrize("name,blocked", [
    ("lfm2_moe", False), ("granitemoehybrid", False), ("afmoe", True)])
def test_a_token_rows_loss_is_whole_or_blocked(name, blocked):
    """Two paths are kept: the two older token rows make the whole
    step's logits and hand them to ``token_cross_entropy`` (their
    compiled steps are the parent's); ``afmoe``'s row asks its model for
    the loss in blocks of positions. Either way the loss function
    returns ``(loss, metrics with the row's counters, {})``."""
    row = family_of(name)
    seen = {}

    def apply_fn(variables, tokens, segment_ids, positions, **kwargs):
        seen.update(kwargs)
        counters = {k: jnp.zeros((), jnp.int32)
                    for k in row.step_counters if k != "tokens"}
        if kwargs.get("blocked_loss"):
            zero = jnp.zeros(())
            return (zero, {"loss": zero,
                           "tokens": jnp.zeros((), jnp.int32)}), counters
        return jnp.zeros(tokens.shape + (16,)), counters

    ids = jnp.zeros((2, 8), jnp.int32)
    loss, metrics, mutated = row.make_loss(TrainConfig(model_family=name),
                                           False)(
        apply_fn, {"params": {}}, {"tokens": ids, "segment_ids": ids,
                                   "positions": ids}, {}, 0)
    assert bool(seen.get("blocked_loss")) == blocked
    assert set(metrics) == {"loss", *row.step_counters} and mutated == {}


@pytest.mark.parametrize("cli,argv,offers", [
    ("train", [], tuple(FAMILIES)),
    ("evaluate", ["--model", "random", "--dataset", "golden"],
     FLOW_FAMILIES),
    ("demo", ["--model", "random"], FLOW_FAMILIES),
])
def test_a_cli_offers_the_tables_rows(cli, argv, offers, capsys):
    """``--model_family``'s choices are the table's keys; the two CLIs
    that need a predictor leave the token rows out."""
    main = importlib.import_module(f"raft_tpu.{cli}").main
    with pytest.raises(SystemExit):
        main(argv + ["--model_family", "nope"])
    said = capsys.readouterr().err
    choices = re.search(r"invalid choice: 'nope' \(choose from (.*)\)",
                        said).group(1)
    assert tuple(re.findall(r"\w+", choices)) == offers
    assert not set(REMOVED) & set(offers)


@pytest.mark.parametrize("kwargs,says", [
    ({"model_path": "random", "small": True},
     "small applies to the canonical RAFT family only; the sparse "
     "family is built from its own config and would silently ignore it"),
    ({"model_path": "random", "alternate_corr": True},
     "alternate_corr applies to the canonical RAFT family only"),
    ({"model_path": "weights.pth"},
     "torch-checkpoint conversion covers the canonical RAFT family only "
     "(no published sparse weights exist); load this family from an "
     "orbax run directory"),
])
def test_load_predictor_refuses_what_the_sparse_row_lacks(kwargs, says):
    from raft_tpu.evaluate import load_predictor
    with pytest.raises(ValueError) as err:
        load_predictor(model_family="sparse", **kwargs)
    assert says in str(err.value)


def test_spatial_shards_are_refused_for_the_token_row():
    from raft_tpu.parallel.mesh import validate_spatial_shards
    with pytest.raises(ValueError, match="canonical RAFT family only "
                                         r"\(got model_family='lfm2_moe'"):
        validate_spatial_shards(2, "lfm2_moe")
    validate_spatial_shards(1, "lfm2_moe")
    validate_spatial_shards(2, "raft")


def _compares_with_a_literal(node) -> bool:
    sides = [node.left, *node.comparators]

    def names_the_family(side):
        return "model_family" in (getattr(side, "id", None),
                                  getattr(side, "attr", None))

    def literal(side):
        if isinstance(side, (ast.Tuple, ast.List, ast.Set)):
            return any(literal(e) for e in side.elts)
        return isinstance(side, ast.Constant) and isinstance(side.value,
                                                             str)

    return any(names_the_family(s) for s in sides) and \
        any(literal(s) for s in sides)


def test_no_module_but_the_table_compares_a_familys_name():
    """Under ``raft_tpu/`` a ``model_family`` meets a string literal
    (``==``, ``!=``, ``in (...)``) in ``families.py`` alone: a call
    site asks the row."""
    found = []
    for folder, _, files in os.walk(os.path.join(REPO, "raft_tpu")):
        for name in files:
            path = os.path.join(folder, name)
            if not name.endswith(".py") or name == "families.py":
                continue
            with open(path) as f:
                tree = ast.parse(f.read())
            found += [f"{os.path.relpath(path, REPO)}:{node.lineno}"
                      for node in ast.walk(tree)
                      if isinstance(node, ast.Compare)
                      and _compares_with_a_literal(node)]
    assert found == []
    # the scan sees what it is for
    assert _compares_with_a_literal(
        ast.parse("tcfg.model_family in ('a', 'b')").body[0].value)
    assert _compares_with_a_literal(
        ast.parse("'raft' != model_family").body[0].value)


def test_training_and_evaluation_import_no_serving_tier(multidevice_child):
    """The compile counter lives in ``utils/``: importing the train loop
    and the dataset pass runs no module of ``raft_tpu/serving``, and the
    tier's exported counter is that one object."""
    out = multidevice_child("""
        import raft_tpu.train, raft_tpu.evaluate, raft_tpu.demo
        loaded = sorted(m for m in sys.modules
                        if m.startswith("raft_tpu.serving"))
        import raft_tpu.serving.metrics as metrics
        import raft_tpu.utils.compile_count as compile_count
        same = (metrics.xla_compile_count
                is compile_count.xla_compile_count
                and metrics.CompileWatch is compile_count.CompileWatch)
        print("RESULT " + json.dumps({"loaded": loaded, "same": same}))
    """)
    assert out == {"loaded": [], "same": True}


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
