"""The token family (``lfm2_moe``): the program against the plain
reference (``benchmark/reference/lfm2.py``) at a small size on the CPU,
the causal paths, the routing, the share against the whole, the Pallas
calls against their jnp twins, the loader, and ``train()`` end to end.

Small size: hidden 64, 8 experts of which 2 are held, top 2, one dense
and two expert layers (conv, attention, conv), vocabulary 256 of which
64 are held, sequences of 128.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2 as reference
from raft_tpu.config import LMConfig, TrainConfig
from raft_tpu.models.lfm2 import LFM2
from raft_tpu.parallel import create_train_state, make_train_step

S = 128
SMALL = LMConfig(
    hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
    num_hidden_layers=3, layer_types=("conv", "full_attention", "conv"),
    num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
    num_experts=8, num_experts_per_tok=2, vocab_size=256,
    experts_held=2, expert_offset=2, vocab_held=64,
    mixed_precision=False)


def ref_cfg(cfg: LMConfig) -> dict:
    keys = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "use_expert_bias", "norm_eps",
            "rope_theta", "expert_offset")
    return {k: getattr(cfg, k) for k in keys}


def seeded_params(cfg: LMConfig, seed: int = 0):
    """Weights at a scale that keeps every stage alive: matrices
    ``normal / sqrt(fan_in)``, norm weights near 1, a real selection
    bias."""
    shapes = jax.eval_shape(
        LFM2(cfg).init, jax.random.PRNGKey(0),
        *(jnp.zeros((1, 8), jnp.int32),) * 3)["params"]
    rng = np.random.default_rng(seed)

    def make(path, leaf):
        name = path[-1].key
        if name.endswith("norm"):
            return 1.0 + 0.1 * rng.standard_normal(leaf.shape)
        if name == "expert_bias":
            return 0.05 * rng.standard_normal(leaf.shape)
        if name == "conv":
            return 0.5 * rng.standard_normal(leaf.shape)
        if name == "embed_tokens":
            return rng.standard_normal(leaf.shape)
        return rng.standard_normal(leaf.shape) * leaf.shape[-2] ** -0.5

    return jax.tree_util.tree_map_with_path(
        lambda p, leaf: jnp.asarray(make(p, leaf), jnp.float32), shapes)


def packed_batch(seed: int = 0, batch: int = 2, vocab: int = 64,
                 cuts=((40, 90), (17,))):
    """Sequences of documents cut at ``cuts`` (one tuple a sequence)."""
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
    return LFM2(cfg).apply({"params": params}, batch["tokens"],
                           batch["segment_ids"], batch["positions"])


def reference_logits(cfg, params, batch, operand=reference.identity):
    return jnp.stack([
        reference.forward(params, batch["tokens"][b],
                          batch["segment_ids"][b], batch["positions"][b],
                          ref_cfg(cfg), operand)
        for b in range(batch["tokens"].shape[0])])


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.fixture(scope="module")
def small():
    return SMALL, seeded_params(SMALL), packed_batch()


# --------------------------------------------- program against the reference

def test_forward_logits_match_reference(small):
    """float32 against float32 at HIGHEST on the CPU: what is left is the
    order of summation (sorted rows against a masked loop, einsum
    against dot), 1e-6 relative; 2e-5 leaves room and still fails
    bfloat16 operands (read: 6e-3) by two orders."""
    cfg, params, batch = small
    ours, counters = program_logits(cfg, params, batch)
    theirs = reference_logits(cfg, params, batch)
    assert rel(ours, theirs) < 2e-5
    assert int(counters["dropped"]) == 0
    mixed, _ = program_logits(dataclasses.replace(cfg, mixed_precision=True),
                              params, batch)
    assert rel(mixed, theirs) > 1e-3          # the limit above is tight
    assert rel(mixed, theirs) < 3e-2          # and bfloat16 is no fault
    rounded = reference_logits(cfg, params, batch, reference.bf16_operand)
    assert rel(rounded, theirs) > 1e-3


def _program_loss(cfg):
    from raft_tpu.families import FAMILIES
    loss_fn = FAMILIES["lfm2_moe"].make_loss(TrainConfig(
        model_family="lfm2_moe"), False)
    model = LFM2(cfg)

    def loss(params, batch):
        value, metrics, _ = loss_fn(model.apply, {"params": params}, batch,
                                    {}, 0)
        return value, metrics
    return loss


def test_loss_and_gradients_match_reference(small):
    """Loss to 1e-6 relative (one float32 mean over 250 positions);
    gradients by leaf to 5e-5 of the leaf's norm (the backward pass
    doubles the forward's reordered sums); bfloat16 operands read 1e-2
    on the worst leaf."""
    cfg, params, batch = small
    (loss, metrics), grads = jax.value_and_grad(
        _program_loss(cfg), has_aux=True)(params, batch)
    ref_loss, ref_grads = reference.loss_and_grads(params, batch,
                                                   ref_cfg(cfg))
    assert abs(float(loss) - float(ref_loss)) < 1e-6 * float(ref_loss)
    counted = np.asarray(reference.counted_positions(batch["segment_ids"]))
    assert int(metrics["tokens"]) == counted.sum() == 2 * S - 2 - 3
    gaps = jax.tree.map(rel, grads, ref_grads)
    bias = gaps["layers_1"]["feed_forward"].pop("expert_bias")
    gaps["layers_2"]["feed_forward"].pop("expert_bias")
    assert bias == 0.0                      # no gradient reaches it
    worst = max(jax.tree.leaves(gaps))
    assert worst < 5e-5, gaps
    _, mixed = jax.value_and_grad(_program_loss(dataclasses.replace(
        cfg, mixed_precision=True)), has_aux=True)(params, batch)
    mixed_gaps = jax.tree.map(rel, mixed, ref_grads)
    assert max(jax.tree.leaves(mixed_gaps)) > 1e-3


def test_three_adamw_steps_match_reference(small):
    """The real step (``make_train_step``: clip, AdamW through
    ``fetch_optimizer``, the guard) against the reference's for three
    steps: each loss to 1e-5 relative, and each leaf's three-step change
    to 2e-3 of its norm (Adam divides by sqrt(v): where a gradient
    element is nought to rounding the quotient is not, so a few
    elements of a leaf move differently); the selection bias does not
    move at all."""
    cfg, params, _ = small
    tcfg = TrainConfig(model_family="lfm2_moe", lr=3e-4, wdecay=0.1,
                       num_steps=1000, batch_size=2, seq_len=S)
    state = create_train_state(jax.random.PRNGKey(0), LFM2(cfg), tcfg)
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
    change = jax.tree.map(lambda a, b: a - b, state.params, params)
    ref_change = jax.tree.map(lambda a, b: a - b, ref_params, params)
    gaps = jax.tree.map(rel, change, ref_change)
    for layer in ("layers_1", "layers_2"):
        gaps[layer]["feed_forward"].pop("expert_bias")
        assert float(jnp.abs(change[layer]["feed_forward"][
            "expert_bias"]).max()) == 0.0
    assert max(jax.tree.leaves(gaps)) < 2e-3, gaps


# -------------------------------------------------------- the causal paths

def test_later_tokens_do_not_reach_earlier_logits(small):
    cfg, params, batch = small
    base, _ = program_logits(cfg, params, batch)
    t = 70
    changed = dict(batch, tokens=batch["tokens"].at[:, t + 1:].set(
        (batch["tokens"][:, t + 1:] + 7) % cfg.vocab))
    after, _ = program_logits(cfg, params, changed)
    np.testing.assert_array_equal(np.asarray(base[:, :t + 1]),
                                  np.asarray(after[:, :t + 1]))
    assert float(jnp.abs(base[:, t + 1:] - after[:, t + 1:]).max()) > 1e-3


def test_a_document_sees_the_one_before_only_through_the_convolution():
    """Attention and RoPE stop at a document's start; the convolution
    runs across the packed sequence (the published module has no
    boundaries), so a document sees the one packed before it through
    the convolution's 2 positions and through nothing else: with the
    one convolution layer first, those are the previous document's last
    two tokens."""
    batch = packed_batch(batch=1, cuts=((40, 90),))
    def shifted(lo, hi):
        return dict(batch, tokens=batch["tokens"].at[:, lo:hi].set(
            (batch["tokens"][:, lo:hi] + 5) % 64))

    for layer_types, clean in ((("full_attention",) * 3, (0, 40)),
                               (("conv", "full_attention",
                                 "full_attention"), (0, 38))):
        cfg = dataclasses.replace(SMALL, layer_types=layer_types)
        params = seeded_params(cfg)
        base, _ = program_logits(cfg, params, batch)
        other, _ = program_logits(cfg, params, shifted(*clean))
        np.testing.assert_allclose(np.asarray(base[:, 40:]),
                                   np.asarray(other[:, 40:]), atol=1e-5)
        assert float(jnp.abs(base[:, :40] - other[:, :40]).max()) > 1e-3
    through, _ = program_logits(cfg, params, shifted(38, 40))
    assert float(jnp.abs(base[:, 40:90] - through[:, 40:90]).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(base[:, 90:]),
                               np.asarray(through[:, 90:]), atol=1e-5)


# --------------------------------------------------------------- the routing

@pytest.mark.parametrize("expert,held", [(3, True), (6, False)])
def test_router_forced_to_one_expert_keeps_every_token(expert, held):
    """Every token ranks ``expert`` first: if it is held here its group
    is the whole batch (no capacity, nothing dropped); if it is not,
    nothing falls here from it."""
    from raft_tpu.models.lfm2 import ExpertFFN
    cfg = dataclasses.replace(SMALL, num_experts_per_tok=1)
    layer = ExpertFFN(cfg)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, S, 64)),
                    jnp.float32)
    params = seeded_params(cfg)["layers_1"]["feed_forward"]
    params = dict(params, expert_bias=jnp.zeros(8).at[expert].set(10.0))
    out, counters = layer.apply({"params": params}, x)
    assert int(counters["dropped"]) == 0
    assert int(counters["routed_here"]) == (2 * S if held else 0)
    assert int(counters["expert_load_max"]) == (2 * S if held else 0)
    theirs = reference.expert_ffn(x.reshape(-1, 64), params, ref_cfg(cfg),
                                  reference.identity).reshape(x.shape)
    if held:
        assert rel(out, theirs) < 1e-5
    else:
        assert float(jnp.abs(out).max()) == 0.0
        assert float(jnp.abs(theirs).max()) == 0.0


# ------------------------------------------------- the share and the whole

def test_shares_add_up_to_the_whole_layer_and_head():
    """The parts that the four expert shares (2 of 8 each) give add up
    to the uncut reference's whole expert layer, and the four vocabulary
    slices' logits side by side are the uncut head's."""
    from raft_tpu.models.lfm2 import ExpertFFN
    whole_cfg = dataclasses.replace(SMALL, experts_held=None,
                                    expert_offset=0, vocab_held=None)
    whole = seeded_params(whole_cfg)
    ffn = whole["layers_1"]["feed_forward"]
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2 * S, 64)),
                    jnp.float32)
    total = reference.expert_ffn(x, ffn, ref_cfg(whole_cfg),
                                 reference.identity)
    parts, routed = 0.0, 0
    for offset in (0, 2, 4, 6):
        cfg = dataclasses.replace(SMALL, expert_offset=offset)
        share = dict(ffn, **{k: ffn[k][offset:offset + 2]
                             for k in ("w1", "w2", "w3")})
        out, counters = ExpertFFN(cfg).apply({"params": share}, x)
        parts = parts + out
        routed += int(counters["routed_here"])
    assert routed == 2 * S * SMALL.num_experts_per_tok
    assert rel(parts, total) < 1e-5

    # every chip runs the same trunk on the same inputs (ids of slice 0
    # here); chip k's head is the program's head over its own rows
    from raft_tpu.models.lfm2 import lm_head
    batch = packed_batch(vocab=64)
    logits, _ = program_logits(whole_cfg, whole, batch)
    held = dataclasses.replace(whole_cfg, vocab_held=64)
    first = dict(whole, embed_tokens=whole["embed_tokens"][:64])
    (slice0, _), sown = LFM2(held).apply(
        {"params": first}, batch["tokens"], batch["segment_ids"],
        batch["positions"], mutable=["intermediates"])
    hidden = sown["intermediates"]["final_hidden"][0]
    side_by_side = jnp.concatenate([
        lm_head(hidden, whole["embed_tokens"][lo:lo + 64], jnp.float32)
        for lo in range(0, 256, 64)], -1)
    np.testing.assert_array_equal(np.asarray(side_by_side[..., :64]),
                                  np.asarray(slice0))
    assert rel(side_by_side, logits) < 1e-6


# --------------------------------------------- the Pallas calls, interpreted

@pytest.mark.pallas_interpret
def test_expert_gmm_kernel_matches_twin():
    from raft_tpu.ops.gmm import expert_gmm
    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.standard_normal((512, 128)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((2, 128, 256)), jnp.float32)
    sizes = jnp.asarray([100, 50, 30, 120, 0, 60, 100, 52], jnp.int32)

    def run(impl):
        def f(a, w):
            out = expert_gmm(a, w, sizes, 2, impl=impl,
                             tiling=(128, 128, 128))
            return (out ** 2).sum(), out
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(lhs, rhs)

    (_, out_k), (da_k, dw_k) = run("pallas")
    (_, out_t), (da_t, dw_t) = run("xla")
    assert float(jnp.abs(out_k[:150]).max()) == 0.0     # experts 0, 1
    assert float(jnp.abs(out_k[300:]).max()) == 0.0     # experts 4..7
    for a, b in ((out_k, out_t), (da_k, da_t), (dw_k, dw_t)):
        assert rel(a, b) < 1e-5


@pytest.mark.pallas_interpret
def test_attention_kernel_matches_twin():
    from raft_tpu.ops.attention import causal_attention
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 4, 256, 64)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, 2, 256, 64)), jnp.float32)
            for _ in range(2))
    seg = jnp.asarray(np.repeat([[0, 1, 2, 2], [0, 0, 1, 1]], 64, axis=1),
                      jnp.int32)

    def run(impl):
        def f(q, k, v):
            out = causal_attention(q, k, v, seg, scale=0.125, impl=impl,
                                   block=128)
            return (out ** 2).sum(), out
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)

    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():
        (_, out_k), grads_k = run("pallas")
    (_, out_t), grads_t = run("xla")
    assert rel(out_k, out_t) < 1e-5
    for a, b in zip(grads_k, grads_t):
        assert rel(a, b) < 1e-4


def test_kernels_refuse_shapes_they_cannot_tile():
    from raft_tpu.ops.attention import causal_attention
    from raft_tpu.ops.gmm import expert_gmm, fit_tiling
    assert fit_tiling(131072, 2048, 1536) == (512, 1024, 768)
    assert fit_tiling(100, 64, 48) is None
    with pytest.raises(ValueError, match="no tiling"):
        expert_gmm(jnp.zeros((100, 64)), jnp.zeros((2, 64, 48)),
                   jnp.zeros((8,), jnp.int32), impl="pallas")
    q = jnp.zeros((1, 2, 100, 64))
    with pytest.raises(ValueError, match="does not tile"):
        causal_attention(q, q, q, jnp.zeros((1, 100), jnp.int32),
                         scale=1.0, impl="pallas")


# ---------------------------------------------------------------- the loader

def test_token_loader_packs_and_resumes(tmp_path):
    from raft_tpu.data.datasets import fetch_dataloader
    from raft_tpu.data.tokens import TokenLoader
    loader = fetch_dataloader("chairs", 2, None, seed=5,
                              tokens={"seq_len": 512, "vocab": 64})
    assert isinstance(loader, TokenLoader)
    it = iter(loader)
    first, second = next(it), next(it)
    for batch in (first, second):
        assert all(batch[k].shape == (2, 512) and batch[k].dtype == np.int32
                   for k in ("tokens", "segment_ids", "positions"))
        seg, pos = batch["segment_ids"], batch["positions"]
        starts = np.diff(seg, axis=1) != 0
        assert (np.diff(seg, axis=1)[starts] == 1).all()
        assert (pos[:, 1:][starts] == 0).all() and (pos[:, 0] == 0).all()
        assert (np.diff(pos, axis=1)[~starts] == 1).all()
        assert batch["tokens"].min() >= 0 and batch["tokens"].max() < 64
    assert not np.array_equal(first["tokens"], second["tokens"])
    assert loader.state().to_dict()["pos"] == 4
    again = TokenLoader(2, 512, 64, seed=5)
    again.load_state({"seed": 5, "epoch": 0, "pos": 2})
    np.testing.assert_array_equal(next(iter(again))["tokens"],
                                  second["tokens"])
    with pytest.raises(ValueError, match="multiple"):
        again.load_state({"seed": 5, "epoch": 0, "pos": 3})

    path = str(tmp_path / "stream.npz")
    stream = np.arange(2048) % 64
    np.savez(path, tokens=stream, offsets=np.array([0, 300, 512, 900]))
    filed = TokenLoader(2, 512, 64, token_file=path)
    batch = next(iter(filed))
    np.testing.assert_array_equal(batch["tokens"][1], stream[512:1024])
    assert batch["segment_ids"][0, 299] == 0 and \
        batch["segment_ids"][0, 300] == 1 and batch["positions"][0, 300] == 0
    assert batch["segment_ids"][1, 0] == 0 and \
        batch["segment_ids"][1, 900 - 512] == 1
    assert len(filed) == 2


def test_the_model_refuses_a_mesh_on_tpu(monkeypatch):
    """On TPU over more than one device the model has no path that fits
    (no ``shard_map`` wrapper, twins too large): it says so at trace
    time. On the CPU the same mesh traces (the tests' train loop does)."""
    from jax.sharding import Mesh

    from raft_tpu.models import lfm2
    from raft_tpu.parallel.spatial import spatial_kernel_mesh
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2, 1),
                ("data", "spatial"))
    with spatial_kernel_mesh(mesh):
        lfm2._refuse_a_mesh_on_tpu()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(NotImplementedError, match="shard_map"):
            lfm2._refuse_a_mesh_on_tpu()
    lfm2._refuse_a_mesh_on_tpu()
