"""The sliding-window family (``afmoe``): the program against the plain
reference (``benchmark/reference/afmoe.py``) at a small size on the CPU,
the share against the whole, the windowed attention (kernel, twin and a
literal loop), the two kinds of layer and their positions, the blocked
loss, planted faults, and the published configuration.

Small size: hidden 64, 4 query and 2 key-value heads of 16, a window of
32, one dense and two expert layers (sliding, sliding, full), 8 experts
of which 2 are held, top 2, one shared expert, vocabulary 256 of which
64 are held, sequences of 128.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import afmoe as reference
from raft_tpu.config import AfmoeConfig, TrainConfig
from raft_tpu.models import afmoe
from raft_tpu.models.afmoe import Afmoe
from raft_tpu.parallel import create_train_state, make_train_step

CONFIG_FILE = "benchmark/configs/trinity_mini.json"
S = 128
SMALL = AfmoeConfig(
    hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
    num_hidden_layers=3,
    layer_types=("sliding_attention", "sliding_attention", "full_attention"),
    num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, sliding_window=32, num_experts=8, num_experts_per_tok=2,
    vocab_size=256, experts_held=2, expert_offset=2, vocab_held=64,
    mixed_precision=False)


def ref_cfg(cfg: AfmoeConfig) -> dict:
    keys = ("hidden_size", "num_hidden_layers", "layer_types",
            "num_dense_layers", "num_attention_heads", "num_key_value_heads",
            "head_dim", "sliding_window", "num_experts_per_tok",
            "route_norm", "route_scale", "mup_enabled", "rms_norm_eps",
            "rope_theta", "expert_offset")
    return {k: getattr(cfg, k) for k in keys}


def seeded_params(cfg: AfmoeConfig, seed: int = 0):
    """Weights at a scale that keeps every stage alive: matrices
    ``normal / sqrt(fan_in)``, norm weights near 1, a real selection
    bias."""
    shapes = jax.eval_shape(
        Afmoe(cfg).init, jax.random.PRNGKey(0),
        *(jnp.zeros((1, 8), jnp.int32),) * 3)["params"]
    rng = np.random.default_rng(seed)

    def make(path, leaf):
        name = path[-1].key
        if name.endswith("norm"):
            return 1.0 + 0.1 * rng.standard_normal(leaf.shape)
        if name == "expert_bias":
            return 0.05 * rng.standard_normal(leaf.shape)
        if name == "embed_tokens":
            return rng.standard_normal(leaf.shape) * leaf.shape[-1] ** -0.5
        return rng.standard_normal(leaf.shape) * leaf.shape[-2] ** -0.5

    return jax.tree_util.tree_map_with_path(
        lambda p, leaf: jnp.asarray(make(p, leaf), jnp.float32), shapes)


def packed_batch(seed: int = 0, batch: int = 2, vocab: int = 64,
                 cuts=((40, 100), (17,))):
    """Sequences of documents cut at ``cuts`` (one tuple a sequence):
    documents shorter (17, 28) and longer (40, 60, 111) than the window
    of 32."""
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


def rel(a, b):
    """Largest gap against the reference's largest magnitude."""
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-12))


def program_logits(cfg, params, batch):
    with jax.default_matmul_precision("highest"):
        return Afmoe(cfg).apply({"params": params}, batch["tokens"],
                                batch["segment_ids"], batch["positions"])


def reference_logits(cfg, params, batch):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            reference.forward(params, batch["tokens"][b],
                              batch["segment_ids"][b],
                              batch["positions"][b], ref_cfg(cfg))
            for b in range(batch["tokens"].shape[0])])


def program_loss(cfg):
    """The family's own loss: head and loss in blocks of positions."""
    def f(params, batch):
        with jax.default_matmul_precision("highest"):
            (loss, metrics), counters = Afmoe(cfg).apply(
                {"params": params}, batch["tokens"], batch["segment_ids"],
                batch["positions"], blocked_loss=True)
        return loss, dict(metrics, **counters)
    return f


@pytest.fixture(scope="module")
def small():
    return SMALL, seeded_params(SMALL), packed_batch()


# ------------------------------------------------- against the reference

def test_forward_logits_match_reference(small):
    cfg, params, batch = small
    ours, counters = program_logits(cfg, params, batch)
    theirs = reference_logits(cfg, params, batch)
    assert ours.shape == (2, S, 64) and ours.dtype == jnp.float32
    assert rel(ours, theirs) < 2e-5
    assert float(jnp.abs(theirs).max()) > 1.0       # the stages are alive
    assert int(counters["dropped"]) == 0
    assert 0 < int(counters["routed_here"]) < 2 * 2 * S * 2
    lengths = np.array([40, 60, 28, 17, 111])
    short = np.minimum(lengths, 32)
    assert int(counters["causal_pairs"]) == int(
        (lengths * (lengths + 1) // 2).sum())
    assert int(counters["window_pairs"]) == int(
        (short * (short + 1) // 2 + (lengths - short) * 32).sum())


def test_loss_and_gradients_match_reference(small):
    """Loss and every leaf's gradient in float32; the selection bias
    takes none; under the mixed policy the gradients differ visibly
    (the comparison can see a precision)."""
    cfg, params, batch = small
    (loss, metrics), grads = jax.value_and_grad(
        program_loss(cfg), has_aux=True)(params, batch)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = reference.loss_and_grads(params, batch,
                                                       ref_cfg(cfg))
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    counted = reference.counted_positions(batch["segment_ids"])
    assert int(metrics["tokens"]) == counted.sum() == 2 * S - 2 - 3
    gaps = jax.tree.map(rel, grads, ref_grads)
    for layer in ("layers_1", "layers_2"):
        assert gaps[layer]["mlp"].pop("expert_bias") == 0.0
    assert max(jax.tree.leaves(gaps)) < 5e-5, gaps
    _, mixed = jax.value_and_grad(program_loss(dataclasses.replace(
        cfg, mixed_precision=True)), has_aux=True)(params, batch)
    assert max(jax.tree.leaves(jax.tree.map(rel, mixed, ref_grads))) > 1e-3


def test_three_adamw_steps_match_reference(small):
    """The real step (``make_train_step``: the blocked loss through the
    family's row, clip, AdamW through ``fetch_optimizer``, the guard)
    against the reference's for three steps."""
    cfg, params, _ = small
    tcfg = TrainConfig(model_family="afmoe", lr=3e-4, wdecay=0.1,
                       num_steps=1000, batch_size=2, seq_len=S)
    state = create_train_state(jax.random.PRNGKey(0), Afmoe(cfg), tcfg)
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
        with jax.default_matmul_precision("highest"):
            ref_params, opt, ref_loss, _ = ref_step(ref_params, opt,
                                                    batch, n)
        assert abs(float(metrics["loss"]) - float(ref_loss)) \
            < 1e-4 * float(ref_loss)
        assert float(metrics["skipped_steps"]) == 0.0
        assert int(metrics["window_pairs"]) < int(metrics["causal_pairs"])
    change = jax.tree.map(lambda a, b: a - b, state.params, params)
    ref_change = jax.tree.map(lambda a, b: a - b, ref_params, params)
    gaps = jax.tree.map(rel, change, ref_change)
    for layer in ("layers_1", "layers_2"):
        gaps[layer]["mlp"].pop("expert_bias")
        assert float(jnp.abs(
            change[layer]["mlp"]["expert_bias"]).max()) == 0.0
    assert max(jax.tree.leaves(gaps)) < 5e-3, gaps


def test_the_decay_mask_spares_the_norms_and_the_bias(small):
    from raft_tpu.optim import _decay_mask
    _, params, _ = small
    spared = {jax.tree_util.keystr(path) for path, keep in
              jax.tree_util.tree_flatten_with_path(_decay_mask(params))[0]
              if not keep}
    assert spared == {
        "['norm']",
        *(f"['layers_{i}']['{name}']" for i in range(3) for name in (
            "input_layernorm", "post_attention_layernorm",
            "pre_mlp_layernorm", "post_mlp_layernorm")),
        *(f"['layers_{i}']['self_attn']['{name}']" for i in range(3)
          for name in ("q_norm", "k_norm")),
        "['layers_1']['mlp']['expert_bias']",
        "['layers_2']['mlp']['expert_bias']"}
    assert set(reference.NO_DECAY) == {s.rsplit("'", 2)[-2] for s in spared}


# --------------------------------------------------- the share and the whole

def _expert_layer(seed=3, n=16):
    """An uncut expert layer's weights (16 experts, top 4) and tokens."""
    rng = np.random.default_rng(seed)
    d, f = 64, 48
    make = lambda *shape: jnp.asarray(                   # noqa: E731
        rng.standard_normal(shape) * shape[-2] ** -0.5, jnp.float32)
    whole = {"router": make(d, n),
             "expert_bias": jnp.asarray(0.05 * rng.standard_normal(n),
                                        jnp.float32),
             "w1": make(n, d, f), "w3": make(n, d, f), "w2": make(n, f, d),
             "shared_expert": {"w1": make(d, f), "w3": make(d, f),
                               "w2": make(f, d)}}
    x = jnp.asarray(rng.standard_normal((2, S, d)), jnp.float32)
    return whole, x


def _shares(whole, x, held=2):
    """The model's expert layer run as each of the ``16 / held`` shares:
    their outputs, and the shared expert's alone."""
    cfg = dataclasses.replace(SMALL, num_experts=16, num_experts_per_tok=4,
                              experts_held=held)
    outs = []
    with jax.default_matmul_precision("highest"):
        for off in range(0, 16, held):
            part = dict(whole, **{k: whole[k][off:off + held]
                                  for k in ("w1", "w3", "w2")})
            out, counters = afmoe.MoE(dataclasses.replace(
                cfg, expert_offset=off)).apply({"params": part}, x)
            assert int(counters["dropped"]) == 0
            outs.append(out)
        shared = afmoe.DenseFFN(cfg, 48, "moe_shared").apply(
            {"params": whole["shared_expert"]}, x)
    return outs, shared


def test_the_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """The share tied to the model: the routed terms of all 8 shares of
    2 experts, plus the shared expert ONCE, equal the uncut reference's
    layer; counting the shared expert with every share does not."""
    whole, x = _expert_layer()
    outs, shared = _shares(whole, x)
    cfg = dict(ref_cfg(SMALL), num_experts_per_tok=4, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([reference.moe(x[b], whole, cfg)
                           for b in range(x.shape[0])])
    routed = sum(out - shared for out in outs)
    assert rel(routed + shared, uncut) < 2e-5
    # planted: the shared expert counted per share
    assert rel(sum(outs), uncut) > 0.5
    # and each share's routed part is its own experts' alone
    assert rel(routed, uncut - shared) < 2e-5
    assert float(jnp.abs(outs[0] - outs[1]).max()) > 1e-2


# ------------------------------------------------------ windowed attention

def _qkv(seed=0, b=2, hq=4, hkv=2, s=256, d=64):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, hq, s, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
            for _ in range(2))
    # documents of 50, 150 and 56; and of 200 and 56
    seg = jnp.asarray(np.stack([np.repeat([0, 1, 2], [50, 150, 56]),
                                np.repeat([0, 1], [200, 56])]), jnp.int32)
    return q, k, v, seg


def literal_attention(q, k, v, seg, scale, window):
    """One query at a time: the softmax over exactly the keys of its
    document at most ``window - 1`` positions back."""
    q, k, v, seg = (np.asarray(a, np.float64) for a in (q, k, v, seg))
    b, hq, s, d = q.shape
    group = hq // k.shape[1]
    out = np.zeros_like(q)
    for bi in range(b):
        for h in range(hq):
            for i in range(s):
                lo = 0 if window is None else max(0, i - window + 1)
                keys = [j for j in range(lo, i + 1)
                        if seg[bi, j] == seg[bi, i]]
                scores = k[bi, h // group, keys] @ q[bi, h, i] * scale
                p = np.exp(scores - scores.max())
                out[bi, h, i] = (p / p.sum()) @ v[bi, h // group, keys]
    return out


@pytest.mark.parametrize("window", [100, 60, None])
def test_the_twin_is_the_literal_loop(window):
    """Documents shorter (50, 56) and longer (150, 200) than the window;
    a window that is no multiple of any block."""
    from raft_tpu.ops.attention import causal_attention_reference
    q, k, v, seg = _qkv(s=256)
    q, k, v, seg = q[:, :, :, :16], k[:, :, :, :16], v[:, :, :, :16], seg
    with jax.default_matmul_precision("highest"):
        twin = causal_attention_reference(q, k, v, seg, scale=0.25,
                                          window=window)
    np.testing.assert_allclose(
        np.asarray(twin), literal_attention(q, k, v, seg, 0.25, window),
        atol=2e-5)


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("window", [100, 128, 60])
def test_the_windowed_kernel_matches_the_twin(window):
    """The block-sparse kernel in interpret mode, blocks of 128: a
    window that is no multiple of the block (100, 60), one that is
    (128); values and gradients."""
    from raft_tpu.ops.attention import causal_attention
    q, k, v, seg = _qkv()

    def run(impl):
        def f(q, k, v):
            out = causal_attention(q, k, v, seg, scale=0.125, impl=impl,
                                   window=window, block=128)
            return (out ** 2).sum(), out
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)

    (_, out_k), grads_k = run("pallas")
    (_, out_t), grads_t = run("xla")
    assert rel(out_k, out_t) < 1e-5
    for a, b in zip(grads_k, grads_t):
        assert rel(a, b) < 1e-4
    # the window cuts real pairs: the unwindowed answer is another
    whole = causal_attention(q, k, v, seg, scale=0.125, impl="xla")
    assert rel(out_t, whole) > 1e-2


@pytest.mark.pallas_interpret
def test_a_window_as_long_as_the_sequence_is_the_unwindowed_op():
    """``window >= seq_len`` masks nothing the causal edge does not: it
    takes the unwindowed path, kernel and twin alike, gradients
    included."""
    from jax.experimental.pallas import tpu as pltpu

    from raft_tpu.ops.attention import causal_attention
    q, k, v, seg = _qkv()

    def run(impl, window):
        def f(q, k, v):
            return (causal_attention(q, k, v, seg, scale=0.125, impl=impl,
                                     window=window, block=128) ** 2).sum()
        return jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)

    for impl in ("xla", "pallas"):
        with pltpu.force_tpu_interpret_mode():
            plain, windowed = run(impl, None), run(impl, 256)
            longer = run(impl, 10 ** 6)
        for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(windowed)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(plain[0]),
                                      np.asarray(longer[0]))


def test_the_windowed_kernel_is_named_apart():
    from raft_tpu.ops.layout import KERNEL_NAMES, kernel_census
    names = list(KERNEL_NAMES)
    assert names.index("attn_window") < names.index("attn")
    line = ('  %splash.1 = f32[8] custom-call(%p), custom_call_target='
            '"tpu_custom_call", frontend_attributes={kernel_metadata={\n'
            '"xprof_metadata":"{\\"block_q\\": 512}"\n'
            '}}, metadata={op_name="jit(step)/jvp(raft_attn_window)/'
            'splash_mqa_fwd/pallas_call"}\n'
            '  %flash.2 = f32[8] custom-call(%p), custom_call_target='
            '"tpu_custom_call", metadata={op_name="jit(step)/raft_attn/'
            'flash_attention/pallas_call"}')
    assert kernel_census(line) == {"attn_window": 1, "attn": 1}


# ------------------------------------------------- the two kinds of layer

def test_full_layers_read_no_positions_and_sliding_layers_do(small):
    """A shift of ``positions`` within the documents' bounds turns the
    sliding layers' rotations by one angle, which cancels in ``q k^T``;
    positions that run on across documents do not cancel for the
    sliding layers and are not read at all by the full ones."""
    cfg, params, batch = small
    run_on = dict(batch, positions=jnp.broadcast_to(
        jnp.arange(S, dtype=jnp.int32) * 3, (2, S)))
    for kinds, moved in ((("full_attention",) * 3, False),
                         (("sliding_attention",) * 3, True),
                         (cfg.layer_types, True)):
        c = dataclasses.replace(cfg, layer_types=kinds)
        base, _ = program_logits(c, params, batch)
        other, _ = program_logits(c, params, run_on)
        if moved:
            assert rel(other, base) > 1e-3
        else:
            np.testing.assert_array_equal(np.asarray(base),
                                          np.asarray(other))


def test_a_sliding_layer_sees_the_window_and_a_full_layer_the_document():
    """One document of 128 tokens, one attention layer: changing token 0
    reaches a sliding layer's logits at positions under 32 and no
    further; it reaches every position of a full layer's."""
    batch = packed_batch(batch=1, cuts=((),))
    changed = dict(batch, tokens=batch["tokens"].at[:, 0].set(
        (batch["tokens"][:, 0] + 5) % 64))
    for kind, reach in (("sliding_attention", 32), ("full_attention", S)):
        cfg = dataclasses.replace(SMALL, num_hidden_layers=1,
                                  layer_types=(kind,), num_dense_layers=1)
        params = seeded_params(cfg)
        base, _ = program_logits(cfg, params, batch)
        other, _ = program_logits(cfg, params, changed)
        gap = np.abs(np.asarray(base - other)).max(axis=(0, 2))
        assert (gap[:reach] > 1e-6).all()
        assert (gap[reach:] == 0).all()


# ------------------------------------------------------------ planted faults

def test_planted_faults_read_as_failures(small, monkeypatch):
    """The comparison that passes above fails with the window left out,
    with the gate left out, and against the reference's own departures
    (window left out, positions on the full layers)."""
    cfg, params, batch = small
    theirs = reference_logits(cfg, params, batch)
    assert rel(program_logits(cfg, params, batch)[0], theirs) < 2e-5

    real = afmoe.causal_attention
    monkeypatch.setattr(
        afmoe, "causal_attention",
        lambda *a, window=None, **kw: real(*a, window=None, **kw))
    assert rel(program_logits(cfg, params, batch)[0], theirs) > 1e-2
    monkeypatch.setattr(afmoe, "causal_attention", real)

    monkeypatch.setattr(afmoe, "_gated",
                        lambda out, gate: out.astype(jnp.float32))
    assert rel(program_logits(cfg, params, batch)[0], theirs) > 1e-2
    monkeypatch.undo()

    (loss, _), grads = jax.value_and_grad(
        program_loss(cfg), has_aux=True)(params, batch)
    for departure in ({"window": False}, {"positions_on_full": True},
                      {"keep_every": 2}):
        with jax.default_matmul_precision("highest"):
            ref_loss, ref_grads = reference.loss_and_grads(
                params, batch, ref_cfg(cfg), **departure)
        gaps = jax.tree.map(rel, grads, ref_grads)
        assert max(jax.tree.leaves(gaps)) > 1e-2, departure
        assert abs(float(loss) - float(ref_loss)) > 1e-4 * float(ref_loss)


# ------------------------------------------------------------ the blocked loss

@pytest.mark.parametrize("block", [64, 32, 256, 100])
def test_the_blocked_loss_is_the_whole_one(block):
    """Value and gradient (hidden states and head) of the loss in blocks
    of positions against the whole logits' loss; a block that does not
    divide the positions (100) falls back to the whole."""
    from raft_tpu.losses import (blocked_token_cross_entropy,
                                 token_cross_entropy)
    rng = np.random.default_rng(0)
    hidden = jnp.asarray(rng.standard_normal((2, S, 64)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((64, 96)) / 8, jnp.float32)
    batch = packed_batch(vocab=96)

    def whole(hidden, head):
        logits = jnp.einsum("bsd,dv->bsv", hidden, head,
                            precision=jax.lax.Precision.HIGHEST)
        return token_cross_entropy(logits, batch["tokens"],
                                   batch["segment_ids"])

    def blocked(hidden, head):
        with jax.default_matmul_precision("highest"):
            return blocked_token_cross_entropy(
                hidden, head, batch["tokens"], batch["segment_ids"],
                block=block, dtype=jnp.float32)

    (a, ma), ga = jax.value_and_grad(whole, argnums=(0, 1), has_aux=True)(
        hidden, head)
    (b, mb), gb = jax.value_and_grad(blocked, argnums=(0, 1), has_aux=True)(
        hidden, head)
    assert abs(float(a) - float(b)) < 1e-6 * float(a)
    assert int(ma["tokens"]) == int(mb["tokens"]) == 2 * S - 2 - 3
    for x, y in zip(ga, gb):
        assert rel(y, x) < 1e-5


def test_the_blocked_step_never_holds_the_whole_logits():
    """The jitted gradient of the blocked loss has no (positions,
    vocabulary) array; the whole loss's has."""
    from raft_tpu.losses import (blocked_token_cross_entropy,
                                 token_cross_entropy)
    hidden = jnp.zeros((2, S, 64), jnp.float32)
    head = jnp.zeros((64, 96), jnp.float32)
    ids = jnp.zeros((2, S), jnp.int32)

    def shapes(f):
        text = jax.jit(jax.grad(f, argnums=(0, 1))).lower(
            hidden, head).as_text()
        return {(2 * S, 96), (2, S, 96)} & {
            tuple(int(n) for n in dims.split("x"))
            for dims in re.findall(
                r"tensor<((?:\d+x)+\d+)xf32>", text)}

    assert not shapes(lambda h, w: blocked_token_cross_entropy(
        h, w, ids, ids, block=64, dtype=jnp.float32)[0])
    assert shapes(lambda h, w: token_cross_entropy(
        jnp.einsum("bsd,dv->bsv", h, w), ids, ids)[0])


# ------------------------------------------------ the published configuration

def test_the_published_config_file_loads_and_counts_its_parameters():
    import json

    from raft_tpu.train import lm_config_from_json
    cfg = lm_config_from_json(CONFIG_FILE, "afmoe")
    assert isinstance(cfg, AfmoeConfig)
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.sliding_window,
            cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.num_experts, cfg.num_experts_per_tok, cfg.route_scale) == (
        2048, 32, 4, 128, 2048, 6144, 1024, 128, 8, 2.826)
    assert (cfg.held, cfg.expert_offset, cfg.vocab, cfg.num_hidden_layers,
            cfg.num_dense_layers) == (16, 0, 25024, 5, 1)
    assert cfg.layer_types == ("sliding_attention",) * 4 + \
        ("full_attention",)
    published = AfmoeConfig()
    assert published.layer_types.count("full_attention") == 8
    assert published.layer_types[4:8] == cfg.layer_types[1:]
    assert (published.num_hidden_layers, published.num_dense_layers,
            published.vocab, published.held) == (32, 2, 200192, 128)
    dummy = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(Afmoe(cfg).init, jax.random.PRNGKey(0),
                            dummy, dummy, dummy)["params"]
    count = lambda tree: sum(int(np.prod(x.shape))       # noqa: E731
                             for x in jax.tree.leaves(tree))
    assert count(shapes["layers_0"]["self_attn"]) == 27_263_232
    assert count(shapes["layers_0"]) == 65_020_160
    assert count(shapes["layers_4"]) == 134_488_448
    assert count(shapes) == 705_474_304
    # every published key of the catalog's row is in the file, unchanged
    # unless the file lists it as reduced
    with open(CONFIG_FILE) as f:
        on_file = json.load(f)
    for key, value in {"hidden_size": 2048, "head_dim": 128,
                       "num_attention_heads": 32, "num_key_value_heads": 4,
                       "sliding_window": 2048, "intermediate_size": 6144,
                       "moe_intermediate_size": 1024, "num_experts": 128,
                       "num_experts_per_tok": 8, "route_scale": 2.826,
                       "num_shared_experts": 1}.items():
        assert on_file[key] == value and key not in on_file["reduced"]


def test_a_config_that_is_not_the_familys_is_refused():
    with pytest.raises(ValueError, match="layer_types names"):
        dataclasses.replace(SMALL, num_hidden_layers=4)
    with pytest.raises(ValueError, match="unknown layer types"):
        dataclasses.replace(SMALL, layer_types=("conv",) * 3)
    with pytest.raises(ValueError, match="multiple of"):
        dataclasses.replace(SMALL, num_key_value_heads=3)
    with pytest.raises(ValueError, match="are not among"):
        dataclasses.replace(SMALL, expert_offset=7)
    with pytest.raises(ValueError, match="vocab_held"):
        dataclasses.replace(SMALL, vocab_held=512)


def test_the_model_refuses_a_mesh_on_tpu(monkeypatch, small):
    from jax.sharding import Mesh

    from raft_tpu.parallel.spatial import spatial_kernel_mesh
    cfg, params, batch = small
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2, 1),
                ("data", "spatial"))
    with spatial_kernel_mesh(mesh):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(NotImplementedError, match="whole or windowed"):
            jax.eval_shape(lambda: program_logits(cfg, params, batch))
