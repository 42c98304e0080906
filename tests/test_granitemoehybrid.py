"""The state-space hybrid (``granitemoehybrid``): the program against
the plain reference (``benchmark/reference/granitemoehybrid.py``) at a
small size on the CPU, documents that end inside a chunk, the four
scalars of the residual path, the published configuration, the
vocabulary's share against the whole.

Small size: hidden 64, 8 state-space heads of 16 with a state of 16 and
chunks of 16, 4 query and 2 key-value heads, three layers (mamba,
attention, mamba), vocabulary 256 of which 64 are held, sequences of
128.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import granitemoehybrid as reference
from raft_tpu.config import GraniteHybridConfig, TrainConfig
from raft_tpu.models.granitemoehybrid import GraniteMoeHybrid
from raft_tpu.parallel import create_train_state, make_train_step

S = 128
SMALL = GraniteHybridConfig(
    hidden_size=64, shared_intermediate_size=96, num_hidden_layers=3,
    layer_types=("mamba", "attention", "mamba"), num_attention_heads=4,
    num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16,
    mamba_d_state=16, mamba_chunk_size=16, vocab_size=256, vocab_held=64,
    mixed_precision=False)
CONFIG_FILE = "benchmark/configs/granite_4_0_h_micro.json"


def ref_cfg(cfg: GraniteHybridConfig) -> dict:
    keys = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "mamba_n_heads", "mamba_d_head",
            "mamba_d_state", "embedding_multiplier", "attention_multiplier",
            "residual_multiplier", "logits_scaling", "rms_norm_eps")
    return {k: getattr(cfg, k) for k in keys}


def seeded_params(cfg: GraniteHybridConfig, seed: int = 0):
    """The benchmark's seeded weights (``drivers/ssm_train_steps.py``):
    every stage alive, no term multiplied by exactly 0 or 1."""
    from benchmark.drivers.ssm_train_steps import seeded_variables
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                        seeded_variables(cfg, seed)["params"])


def packed_batch(seed: int = 0, batch: int = 2, vocab: int = 64,
                 cuts=((40, 90), (16, 17, 64))):
    """Sequences of documents cut at ``cuts`` (one tuple a sequence):
    boundaries inside a chunk of 16 (40, 90, 17), at a chunk's edge (16,
    64), and a document of one token (16..17)."""
    rng = np.random.default_rng(seed)
    out = {"tokens": rng.integers(0, vocab, (batch, S)).astype(np.int32),
           "segment_ids": np.zeros((batch, S), np.int32),
           "positions": np.zeros((batch, S), np.int32)}
    for b in range(batch):
        edges = [0, *cuts[b % len(cuts)], S]
        for doc, (lo, hi) in enumerate(zip(edges, edges[1:])):
            out["segment_ids"][b, lo:hi] = doc
            out["positions"][b, lo:hi] = np.arange(hi - lo)
    return {k: jnp.asarray(v) for k, v in out.items()}


def program_logits(cfg, params, batch):
    return GraniteMoeHybrid(cfg).apply(
        {"params": params}, batch["tokens"], batch["segment_ids"],
        batch["positions"])


def reference_logits(cfg, params, batch, operand=reference.identity):
    forward = jax.jit(lambda tokens, segment_ids: reference.forward(
        params, tokens, segment_ids, ref_cfg(cfg), operand))
    return jnp.stack([forward(batch["tokens"][b], batch["segment_ids"][b])
                      for b in range(batch["tokens"].shape[0])])


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.fixture(scope="module")
def small():
    return SMALL, seeded_params(SMALL), packed_batch()


# --------------------------------------------- program against the reference

def test_forward_logits_match_reference(small):
    """float32 against float32 on the CPU: what is left is the order of
    summation (a chunked scan against a per-token recurrence, einsum
    against dot), 1e-6 relative; 2e-5 leaves room and still fails
    bfloat16 operands by two orders."""
    cfg, params, batch = small
    ours, counters = program_logits(cfg, params, batch)
    theirs = reference_logits(cfg, params, batch)
    assert rel(ours, theirs) < 2e-5
    assert int(counters["ssm_resets"]) == 2 + 3
    assert int(counters["ssd_chunks"]) == 2 * S // 16
    mixed, _ = program_logits(dataclasses.replace(cfg, mixed_precision=True),
                              params, batch)
    assert rel(mixed, theirs) > 1e-3          # the limit above is tight
    assert rel(mixed, theirs) < 3e-2          # and bfloat16 is no fault
    rounded = reference_logits(cfg, params, batch, reference.bf16_operand)
    assert rel(rounded, theirs) > 1e-3


def _program_loss(cfg):
    from raft_tpu.families import FAMILIES
    loss_fn = FAMILIES["granitemoehybrid"].make_loss(TrainConfig(
        model_family="granitemoehybrid"), False)
    model = GraniteMoeHybrid(cfg)

    def loss(params, batch):
        value, metrics, _ = loss_fn(model.apply, {"params": params}, batch,
                                    {}, 0)
        return value, metrics
    return loss


def test_loss_and_gradients_match_reference(small):
    """Loss to 1e-6 relative; every leaf's gradient to 5e-5 of the
    leaf's norm, the scan's scalars (``A_log``, ``D``, ``dt_bias``) and
    the convolution among them; bfloat16 operands read above 1e-3 on
    the worst leaf."""
    cfg, params, batch = small
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        _program_loss(cfg), has_aux=True))(params, batch)
    ref_loss, ref_grads = jax.jit(lambda p, b: reference.loss_and_grads(
        p, b, ref_cfg(cfg)))(params, batch)
    assert abs(float(loss) - float(ref_loss)) < 1e-6 * float(ref_loss)
    counted = np.asarray(reference.counted_positions(batch["segment_ids"]))
    assert int(metrics["tokens"]) == counted.sum() == 2 * S - 2 - 5
    gaps = jax.tree.map(rel, grads, ref_grads)
    assert set(gaps["layers_0"]["mamba"]) == {
        "in_proj", "conv", "conv_bias", "A_log", "D", "dt_bias", "norm",
        "out_proj"}
    assert max(jax.tree.leaves(gaps)) < 5e-5, gaps
    assert min(float(jnp.abs(g).max())
               for g in jax.tree.leaves(ref_grads)) > 0.0
    _, mixed = jax.jit(jax.value_and_grad(_program_loss(dataclasses.replace(
        cfg, mixed_precision=True)), has_aux=True))(params, batch)
    mixed_gaps = jax.tree.map(rel, mixed, ref_grads)
    assert max(jax.tree.leaves(mixed_gaps)) > 1e-3


def test_the_references_planted_departures_move_it(small):
    """What the controls plant in the reference is seen by the
    program's comparison: the state and convolution carried across
    boundaries, and half the loss positions left out."""
    cfg, params, batch = small
    run = lambda **kw: jax.jit(lambda p, b: reference.loss_and_grads(  # noqa: E731
        p, b, ref_cfg(cfg), **kw))(params, batch)
    loss, grads = run()
    carried, halved = run(resets=False), run(keep_every=2)
    for other_loss, other_grads in (carried, halved):
        gaps = jax.tree.map(rel, other_grads, grads)
        assert max(jax.tree.leaves(gaps)) > 1e-2
    assert abs(float(carried[0]) - float(loss)) > 1e-5


# ------------------------------------------------------ documents and order

def test_a_document_sees_nothing_of_the_one_before(small):
    """State, convolution and attention all stop at a document's first
    token: changing document 0 leaves every later logit where it was
    (document 1 starts inside a chunk), and later tokens never reach
    earlier logits."""
    cfg, params, batch = small
    base, _ = program_logits(cfg, params, batch)
    earlier = dict(batch, tokens=batch["tokens"].at[0, :40].set(
        (batch["tokens"][0, :40] + 5) % cfg.vocab))
    after, _ = program_logits(cfg, params, earlier)
    np.testing.assert_allclose(np.asarray(base[0, 40:]),
                               np.asarray(after[0, 40:]), atol=1e-6)
    assert float(jnp.abs(base[0, :40] - after[0, :40]).max()) > 1e-3
    later = dict(batch, tokens=batch["tokens"].at[:, 71:].set(
        (batch["tokens"][:, 71:] + 7) % cfg.vocab))
    after, _ = program_logits(cfg, params, later)
    np.testing.assert_array_equal(np.asarray(base[:, :71]),
                                  np.asarray(after[:, :71]))
    assert float(jnp.abs(base[:, 71:] - after[:, 71:]).max()) > 1e-3


def test_positions_are_not_read(small):
    cfg, params, batch = small
    base, _ = program_logits(cfg, params, batch)
    moved, _ = program_logits(cfg, params, dict(
        batch, positions=batch["positions"] + 11))
    np.testing.assert_array_equal(np.asarray(base), np.asarray(moved))


# ----------------------------------------------------- the four multipliers

@pytest.mark.parametrize("key,other", [
    ("embedding_multiplier", 6.0), ("attention_multiplier", 0.125),
    ("residual_multiplier", 0.5), ("logits_scaling", 4.0)])
def test_each_multiplier_matters_and_matches_the_reference(small, key,
                                                           other):
    cfg, params, batch = small
    base, _ = program_logits(cfg, params, batch)
    changed = dataclasses.replace(cfg, **{key: other})
    ours, _ = program_logits(changed, params, batch)
    assert rel(ours, base) > 1e-3
    assert rel(ours, reference_logits(changed, params, batch)) < 2e-5


# ------------------------------------------------ the published configuration

def test_the_published_config_file_loads_and_counts_its_parameters():
    from raft_tpu.train import lm_config_from_json
    cfg = lm_config_from_json(CONFIG_FILE, "granitemoehybrid")
    assert isinstance(cfg, GraniteHybridConfig)
    assert (cfg.hidden_size, cfg.d_inner, cfg.mamba_n_heads,
            cfg.mamba_d_state, cfg.shared_intermediate_size, cfg.vocab,
            cfg.num_hidden_layers) == (2048, 4096, 64, 128, 8192, 12544, 10)
    assert cfg.layer_types == ("mamba",) * 5 + ("attention",) + \
        ("mamba",) * 4
    published = GraniteHybridConfig()
    assert published.layer_types[:10] == cfg.layer_types
    assert published.layer_types.count("attention") == 4
    for key in ("embedding_multiplier", "attention_multiplier",
                "residual_multiplier", "logits_scaling", "rms_norm_eps",
                "mamba_chunk_size", "mamba_d_conv", "mamba_expand"):
        assert getattr(cfg, key) == getattr(published, key)
    dummy = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(GraniteMoeHybrid(cfg).init,
                            jax.random.PRNGKey(0), dummy, dummy,
                            dummy)["params"]
    count = lambda tree: sum(int(np.prod(x.shape))       # noqa: E731
                             for x in jax.tree.leaves(tree))
    assert count(shapes["layers_0"]) == 76_182_976
    assert count(shapes["layers_5"]) == 60_821_504
    assert count(shapes) == 772_160_448


def test_a_config_that_is_not_the_familys_is_refused():
    with pytest.raises(ValueError, match="layer_types names"):
        dataclasses.replace(SMALL, num_hidden_layers=4)
    with pytest.raises(ValueError, match="unknown layer types"):
        dataclasses.replace(SMALL, layer_types=("mamba", "conv", "mamba"))
    with pytest.raises(ValueError, match="mamba_expand x hidden_size"):
        dataclasses.replace(SMALL, mamba_n_heads=4)
    with pytest.raises(ValueError, match="mamba_n_groups 1"):
        dataclasses.replace(SMALL, mamba_n_groups=2)
    with pytest.raises(ValueError, match="vocab_held"):
        dataclasses.replace(SMALL, vocab_held=512)


# ------------------------------------------------------ the vocabulary's share

def test_logits_over_a_slice_are_the_wholes_columns(small):
    """The chip's share of the vocabulary: logits over rows 0-63 equal
    columns 0-63 of the logits over all 256 (the same embedding rows,
    ids drawn from the slice)."""
    cfg, params, batch = small
    whole_cfg = dataclasses.replace(cfg, vocab_held=None)
    rng = np.random.default_rng(5)
    rows = jnp.asarray(rng.standard_normal((256, 64)) / 8, jnp.float32)
    whole = dict(params, embed_tokens=rows)
    share = dict(params, embed_tokens=rows[:64])
    ours, _ = program_logits(cfg, share, batch)
    full, _ = program_logits(whole_cfg, whole, batch)
    assert full.shape[-1] == 256 and ours.shape[-1] == 64
    np.testing.assert_allclose(np.asarray(ours), np.asarray(full[..., :64]),
                               rtol=1e-6, atol=1e-6)


# --------------------------------------------------------- the optimizer step

def test_three_adamw_steps_match_reference(small):
    """The real step (``make_train_step``: clip, AdamW through
    ``fetch_optimizer``, the guard) against the reference's for three
    steps: each loss to 1e-5 relative, each leaf's three-step change to
    2e-3 of its norm (Adam divides by sqrt(v): where a gradient element
    is nought to rounding the quotient is not); ``A_log``, ``D``,
    ``dt_bias`` and the norm weights take no decay in either."""
    cfg, params, _ = small
    tcfg = TrainConfig(model_family="granitemoehybrid", lr=3e-4, wdecay=0.1,
                       num_steps=1000, batch_size=2, seq_len=S)
    state = create_train_state(jax.random.PRNGKey(0), GraniteMoeHybrid(cfg),
                               tcfg)
    state = state.replace(params=params)
    step = make_train_step(tcfg, donate=False)
    ref_step = jax.jit(lambda p, o, b, n: reference.train_step(
        p, o, b, n, cfg=ref_cfg(cfg), lr=tcfg.lr,
        total_steps=tcfg.num_steps + 100, wdecay=tcfg.wdecay,
        eps=tcfg.epsilon, clip=tcfg.clip))
    ref_params = params
    opt = {"mu": jax.tree.map(jnp.zeros_like, params),
           "nu": jax.tree.map(jnp.zeros_like, params)}
    for n in range(3):
        batch = packed_batch(seed=10 + n)
        state, metrics = step(state, batch, jax.random.PRNGKey(1))
        ref_params, opt, ref_loss, _ = ref_step(ref_params, opt, batch, n)
        assert abs(float(metrics["loss"]) - float(ref_loss)) \
            < 1e-5 * float(ref_loss)
        assert float(metrics["skipped_steps"]) == 0.0
        assert int(metrics["ssm_resets"]) == 5
    change = jax.tree.map(lambda a, b: a - b, state.params, params)
    ref_change = jax.tree.map(lambda a, b: a - b, ref_params, params)
    gaps = jax.tree.map(rel, change, ref_change)
    assert max(jax.tree.leaves(gaps)) < 2e-3, gaps


def test_the_decay_mask_spares_the_scans_scalars_and_the_norms(small):
    from raft_tpu.optim import _decay_mask
    _, params, _ = small
    mask = _decay_mask(params)
    spared = {jax.tree_util.keystr(path) for path, keep in
              jax.tree_util.tree_flatten_with_path(mask)[0] if not keep}
    assert spared == {
        "['norm']",
        *(f"['layers_{i}']['{name}']" for i in range(3)
          for name in ("input_layernorm", "post_attention_layernorm")),
        *(f"['layers_{i}']['mamba']['{name}']" for i in (0, 2)
          for name in ("A_log", "D", "dt_bias", "norm"))}


def test_the_model_refuses_a_mesh_on_tpu(monkeypatch, small):
    from jax.sharding import Mesh

    from raft_tpu.parallel.spatial import spatial_kernel_mesh
    cfg, params, batch = small
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2, 1),
                ("data", "spatial"))
    with spatial_kernel_mesh(mesh):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(NotImplementedError, match="shard_map"):
            jax.eval_shape(lambda: program_logits(cfg, params, batch))
