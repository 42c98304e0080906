"""Plain reference of the Trinity decoder (arcee-ai ``afmoe``): forward
pass, loss, gradients and the AdamW step in straightforward
``jax.numpy``, float32, every product at ``Precision.HIGHEST``. No
kernels, no sorting of tokens, no skipped blocks, no import from the
program.

Equations (HF ``modeling_afmoe.py``; departures are listed under
``assumed`` in ``benchmark/configs/trinity_mini.json``):

* model: ``x_0 = sqrt(hidden) * embed(ids)`` (``mup_enabled``); the
  layers; a last RMSNorm; an untied head. RMSNorm is
  ``x * rsqrt(mean(x^2) + eps) * w``;
* layer: ``h = x + post_attention_layernorm(Attn(input_layernorm(x)))``,
  ``y = h + post_mlp_layernorm(FFN(pre_mlp_layernorm(h)))``;
* attention, both kinds: ``q = W_q u``, ``k = W_k u``, ``v = W_v u``,
  ``g = W_g u``; RMSNorm with a learned weight over each head's 128
  channels of ``q`` and of ``k``; on ``sliding_attention`` layers only,
  half-rotation RoPE on ``q`` and ``k`` at positions restarting with
  each document; ``softmax(q k^T / sqrt(128))`` over the keys ``j`` of
  query ``i``'s own document with ``0 <= i - j`` (full layer) or
  ``0 <= i - j < sliding_window`` (sliding layer): the full masked
  softmax over all the sequence's keys, a block of queries at a time;
  ``out = W_o (attn * sigmoid(g))``;
* FFN of a leading layer: ``W_2 (silu(W_1 x) * W_3 x)``;
* FFN of the others: ``s = sigmoid(W_r x)`` over all experts; the
  chosen are the ``num_experts_per_tok`` largest of ``s + expert_bias``;
  ``w = s[chosen] / (sum s[chosen] + 1e-20) * route_scale``; a loop
  over the experts held here, each a SwiGLU over every token under a
  mask of the tokens that chose it, times its weight; what absent
  experts would add is left out; the shared expert's SwiGLU over every
  token is added unweighted;
* loss: mean next-token cross-entropy over the positions whose target
  lies in the same document, over the vocabulary held.

``operand`` is applied to both operands of every product the
configuration states in bfloat16 (not the router's, which it states in
float32): ``identity`` for the reference, ``fp8_operand`` for the
control.

So that the published widths fit one chip beside the reference's own
state, the work is blocked: one sequence at a time inside the
differentiated function, one layer recomputed at a time, attention over
blocks of 256 queries (all 16384 keys each), one held expert at a time,
the loss over blocks of 1024 rows. None of it changes a number beyond
the order of float32 sums.

Three planted departures, for the controls only
(``benchmark/tools/swa_control.py``): ``window=False`` lets the sliding
layers attend the whole causal document; ``positions_on_full=True``
rotates ``q`` and ``k`` on the full layers too; ``keep_every=2`` leaves
every second loss position out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def identity(x):
    return x


def fp8_operand(x):
    """Round ``x`` to float8 (e4m3) with one scale per tensor, back in
    float32: the precision below bfloat16. Accumulation stays float32."""
    x = x.astype(jnp.float32)
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def bf16_operand(x):
    """Round ``x`` to bfloat16 and back: the precision the configuration
    states for its products, where a test wants to see what it costs."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def product(x, w, operand):
    return jnp.dot(operand(x), operand(w), precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """``x`` (S, H, D): dimension ``i`` pairs with ``i + D/2``."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(u, p, segment_ids, positions, cfg, operand, *, rotary: bool,
              window, q_block=256):
    """``rotary``: RoPE on ``q`` and ``k``; ``window``: how far back a
    query sees (``None``: the whole causal document)."""
    s = u.shape[0]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    group = hq // hkv
    q = product(u, p["q_proj"], operand).reshape(s, hq, hd)
    k = product(u, p["k_proj"], operand).reshape(s, hkv, hd)
    v = product(u, p["v_proj"], operand).reshape(s, hkv, hd)
    gate = jax.nn.sigmoid(product(u, p["gate_proj"], operand))
    q = rms_norm(q, p["q_norm"], cfg["rms_norm_eps"])
    k = rms_norm(k, p["k_norm"], cfg["rms_norm_eps"])
    if rotary:
        q = rope(q, positions, cfg["rope_theta"])
        k = rope(k, positions, cfg["rope_theta"])
    q = q.reshape(s, hkv, group, hd)
    q_block = min(q_block, s)
    k_op, v_op = operand(k), operand(v)

    @jax.checkpoint
    def block(args):
        q_b, seg_b, at_b = args
        scores = jnp.einsum("qgrd,kgd->grqk", operand(q_b), k_op,
                            precision=HIGHEST) * hd ** -0.5
        back = at_b[:, None] - jnp.arange(s)[None, :]
        allowed = (seg_b[:, None] == segment_ids[None, :]) & (back >= 0)
        if window is not None:
            allowed &= back < window
        probs = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", operand(probs), v_op,
                          precision=HIGHEST)

    blocks = s // q_block
    out = jax.lax.map(block, (
        q.reshape(blocks, q_block, hkv, group, hd),
        segment_ids.reshape(blocks, q_block),
        jnp.arange(s).reshape(blocks, q_block)))
    return product(out.reshape(s, hq * hd) * gate, p["o_proj"], operand)


def swiglu(x, w1, w3, w2, operand):
    return product(jax.nn.silu(product(x, w1, operand))
                   * product(x, w3, operand), w2, operand)


def route(x, p, cfg):
    """The chosen experts and their weights, all in float32."""
    scores = jax.nn.sigmoid(jnp.dot(x, p["router"], precision=HIGHEST))
    ranked = scores + jax.lax.stop_gradient(p["expert_bias"])
    _, chosen = jax.lax.top_k(ranked, cfg["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["route_norm"]:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    return chosen, weight * cfg["route_scale"]


def routed_part(x, p, cfg, operand):
    """What the experts held in ``p`` (experts ``expert_offset`` on)
    add for the tokens that chose them."""
    chosen, weight = route(x, p, cfg)

    @jax.checkpoint
    def one(out, held):
        g, w1, w3, w2 = held
        w_e = jnp.where(chosen == cfg["expert_offset"] + g, weight,
                        0.0).sum(-1)
        return out + w_e[:, None] * swiglu(x, w1, w3, w2, operand), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(p["w1"].shape[0]), p["w1"], p["w3"], p["w2"]))
    return out


def moe(x, p, cfg, operand=identity):
    """The held experts' part of the routed sum plus the shared
    expert's whole output (given every expert and ``expert_offset`` 0:
    the uncut layer)."""
    shared = p["shared_expert"]
    return routed_part(x, p, cfg, operand) + swiglu(
        x, shared["w1"], shared["w3"], shared["w2"], operand)


def layer(x, p, segment_ids, positions, cfg, operand, *, sliding: bool,
          window: bool = True, positions_on_full: bool = False):
    eps = cfg["rms_norm_eps"]
    mixed = attention(
        rms_norm(x, p["input_layernorm"], eps), p["self_attn"],
        segment_ids, positions, cfg, operand,
        rotary=sliding or positions_on_full,
        window=cfg["sliding_window"] if sliding and window else None)
    h = x + rms_norm(mixed, p["post_attention_layernorm"], eps)
    u = rms_norm(h, p["pre_mlp_layernorm"], eps)
    ffn = p["mlp"]
    if "router" in ffn:
        out = moe(u, ffn, cfg, operand)
    else:
        out = swiglu(u, ffn["w1"], ffn["w3"], ffn["w2"], operand)
    return h + rms_norm(out, p["post_mlp_layernorm"], eps)


def final_hidden(params, tokens, segment_ids, positions, cfg,
                 operand=identity, **departures):
    """One sequence: ``tokens`` / ``segment_ids`` / ``positions`` (S,)
    to the hidden states after the last RMSNorm (S, hidden)."""
    x = params["embed_tokens"][tokens]
    if cfg["mup_enabled"]:
        x = x * cfg["hidden_size"] ** 0.5
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(functools.partial(
            layer, cfg=cfg, operand=operand,
            sliding=cfg["layer_types"][i] == "sliding_attention",
            **departures))(
            x, params[f"layers_{i}"], segment_ids, positions)
    return rms_norm(x, params["norm"], cfg["rms_norm_eps"])


def forward(params, tokens, segment_ids, positions, cfg, operand=identity):
    """One sequence's logits (S, vocabulary held)."""
    return product(final_hidden(params, tokens, segment_ids, positions,
                                cfg, operand), params["lm_head"], operand)


def counted_positions(segment_ids, keep_every=1):
    """(..., S) bool: the positions whose target lies in their own
    document (every ``keep_every``-th of them)."""
    s = segment_ids.shape[-1]
    counted = (jnp.roll(segment_ids, -1, axis=-1) == segment_ids) \
        & (jnp.arange(s) < s - 1)
    return counted & (jnp.arange(s) % keep_every == 0)


def sequence_nll(params, tokens, segment_ids, positions, cfg, operand,
                 keep_every=1, row_block=1024, **departures):
    """Sum of the counted positions' cross-entropies in one sequence,
    the logits made a block of rows at a time."""
    hidden = final_hidden(params, tokens, segment_ids, positions, cfg,
                          operand, **departures)
    s = tokens.shape[0]
    row_block = min(row_block, s)

    @jax.checkpoint
    def rows(total, block):
        hidden_b, target_b, counted_b = block
        logits = product(hidden_b, params["lm_head"], operand)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, target_b[:, None],
                                     axis=-1)[:, 0]
        return total + jnp.where(counted_b, logz - picked, 0.0).sum(), None

    blocked = jax.tree.map(
        lambda a: a.reshape(s // row_block, row_block, *a.shape[1:]),
        (hidden, jnp.roll(tokens, -1),
         counted_positions(segment_ids, keep_every)))
    total, _ = jax.lax.scan(rows, jnp.zeros(()), blocked)
    return total


def loss_and_grads(params, batch, cfg, operand=identity, keep_every=1,
                   **departures):
    """Mean loss over the batch's counted positions and its gradient;
    the sequences go through one at a time."""
    n = jnp.maximum(counted_positions(batch["segment_ids"],
                                      keep_every).sum(), 1)

    def mean_nll(params):
        @jax.checkpoint
        def one(total, seq):
            return total + sequence_nll(
                params, seq["tokens"], seq["segment_ids"],
                seq["positions"], cfg, operand, keep_every,
                **departures), None

        total, _ = jax.lax.scan(
            one, jnp.zeros(()),
            {k: batch[k] for k in ("tokens", "segment_ids", "positions")})
        return total / n

    return jax.value_and_grad(mean_nll)(params)


def one_cycle_lr(step, lr, total_steps, pct_start=0.05):
    """PyTorch OneCycleLR, linear anneal, as ``optim.onecycle_schedule``
    states it: ``lr/25 -> lr`` over the first 5 %, then down to
    ``lr/25e4``."""
    warm = max(int(total_steps * pct_start), 1)
    up = lr / 25.0 + (lr - lr / 25.0) * jnp.minimum(step / warm, 1.0)
    frac = jnp.clip((step - warm) / (total_steps - warm), 0.0, 1.0)
    down = lr + (lr / 25.0 / 1e4 - lr) * frac
    return jnp.where(step < warm, up, down)


#: leaves AdamW's decay leaves alone: the selection bias (no gradient
#: reaches it) and every norm weight
NO_DECAY = ("expert_bias", "norm", "input_layernorm",
            "post_attention_layernorm", "pre_mlp_layernorm",
            "post_mlp_layernorm", "q_norm", "k_norm")


def apply_update(params, opt, grads, step, *, lr, total_steps, wdecay,
                 eps=1e-8, clip=1.0, b1=0.9, b2=0.999):
    """Global-norm clip, AdamW (decay on the matrices and the
    embedding), one-cycle rate. Returns the new parameters and moments
    and the clipped gradient."""
    norm = jnp.sqrt(sum(jnp.sum(g ** 2) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-30))
    grads = jax.tree.map(lambda g: g * scale, grads)
    t = step + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["nu"],
                      grads)
    rate = one_cycle_lr(step, lr, total_steps)

    def update(path, p, m, v):
        decay = 0.0 if path[-1].key in NO_DECAY else wdecay
        m_hat = m / (1 - b1 ** t)
        v_hat = v / (1 - b2 ** t)
        return p - rate * (m_hat / (jnp.sqrt(v_hat) + eps) + decay * p)

    new_params = jax.tree_util.tree_map_with_path(update, params, mu, nu)
    return new_params, {"mu": mu, "nu": nu}, grads


def train_step(params, opt, batch, step, *, cfg, lr, total_steps,
               wdecay, eps=1e-8, clip=1.0, b1=0.9, b2=0.999,
               operand=identity, **departures):
    """One step of the program's loop: ``loss_and_grads``, then
    ``apply_update``. Returns the new parameters and moments, the loss
    and the clipped gradient. (At the published widths the benchmark
    runs the two halves as two programs with the moments parked on the
    host meanwhile, as the state-space reference does.)"""
    loss, grads = loss_and_grads(params, batch, cfg, operand, **departures)
    new_params, opt, grads = apply_update(
        params, opt, grads, step, lr=lr, total_steps=total_steps,
        wdecay=wdecay, eps=eps, clip=clip, b1=b1, b2=b2)
    return new_params, opt, loss, grads
