"""Plain reference of the LFM2-MoE decoder (LiquidAI ``lfm2_moe``):
forward pass, loss, gradients and the AdamW step in straightforward
``jax.numpy``, float32, every product at ``Precision.HIGHEST``. No
kernels, no sorting of tokens, no import from the program.

Equations (HF ``modeling_lfm2_moe.py``; departures are listed under
``assumed`` in ``benchmark/configs/lfm2_24b_a2b.json``):

* layer: ``h = x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``;
  RMSNorm ``x * rsqrt(mean(x^2) + eps) * w``; a last RMSNorm, then the
  head, tied to the embedding;
* gated short convolution: ``[B, C, X] = split3(W_in u)``, ``z = B*X``,
  ``c_t = sum_k w_k z_{t-K+1+k}`` (zeros before the sequence's start),
  ``out = W_out (C * c)``;
* attention: 32 query heads and 8 key-value heads of 64, RMSNorm with a
  learned weight on each head's q and k, half-rotation RoPE at positions
  restarting with each document, ``softmax(q k^T / sqrt(64))`` over the
  same document's keys at or before the query: the full masked softmax,
  a block of queries at a time;
* expert layer: ``s = sigmoid(W_g x)`` over all experts,
  ``sel = topk(s + b)``, ``w = s[sel] / (sum s[sel] + 1e-6)`` times
  ``routed_scaling_factor``; a loop over the experts held here, each a
  SwiGLU over every token under a mask of the tokens that selected it;
  what absent experts would add is left out;
* loss: mean next-token cross-entropy over the positions whose target
  lies in the same document, over the vocabulary held.

``operand`` is applied to both operands of every product the
configuration states in bfloat16 (not the router's, which it states in
float32): ``identity`` for the reference, ``fp8_operand`` for the
control. Gradients are accumulated a sequence at a time and each layer
is recomputed in the backward pass, so the published widths fit one
chip beside nothing else.
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


def short_conv(u, p, operand):
    b, c, x = jnp.split(product(u, p["in_proj"], operand), 3, axis=-1)
    z = b * x
    taps = p["conv"]
    k = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, z.shape[1])), z], 0)
    conv = sum(taps[i] * padded[i:i + z.shape[0]] for i in range(k))
    return product(c * conv, p["out_proj"], operand)


def rope(x, positions, theta):
    """``x`` (S, H, D): dimension ``i`` pairs with ``i + D/2``."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(u, p, segment_ids, positions, cfg, operand, q_block=512):
    s = u.shape[0]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // hq
    group = hq // hkv
    q = product(u, p["q_proj"], operand).reshape(s, hq, hd)
    k = product(u, p["k_proj"], operand).reshape(s, hkv, hd)
    v = product(u, p["v_proj"], operand).reshape(s, hkv, hd)
    q = rope(rms_norm(q, p["q_layernorm"], cfg["norm_eps"]), positions,
             cfg["rope_theta"])
    k = rope(rms_norm(k, p["k_layernorm"], cfg["norm_eps"]), positions,
             cfg["rope_theta"])
    q = q.reshape(s, hkv, group, hd)
    q_block = min(q_block, s)
    k_op, v_op = operand(k), operand(v)

    @jax.checkpoint
    def block(args):
        q_b, seg_b, at_b = args
        scores = jnp.einsum("qgrd,kgd->grqk", operand(q_b), k_op,
                            precision=HIGHEST) * hd ** -0.5
        allowed = (seg_b[:, None] == segment_ids[None, :]) \
            & (at_b[:, None] >= jnp.arange(s)[None, :])
        probs = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", operand(probs), v_op,
                          precision=HIGHEST)

    blocks = s // q_block
    out = jax.lax.map(block, (
        q.reshape(blocks, q_block, hkv, group, hd),
        segment_ids.reshape(blocks, q_block),
        jnp.arange(s).reshape(blocks, q_block)))
    return product(out.reshape(s, hq * hd), p["out_proj"], operand)


def swiglu(x, w1, w3, w2, operand):
    return product(jax.nn.silu(product(x, w1, operand))
                   * product(x, w3, operand), w2, operand)


def route(x, p, cfg):
    """Scores, the selected experts and their weights, all in float32."""
    scores = jax.nn.sigmoid(jnp.dot(x, p["router"], precision=HIGHEST))
    ranked = scores
    if cfg["use_expert_bias"]:
        ranked = scores + jax.lax.stop_gradient(p["expert_bias"])
    _, sel = jax.lax.top_k(ranked, cfg["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-6)
    return sel, weight * cfg["routed_scaling_factor"]


def expert_ffn(x, p, cfg, operand):
    sel, weight = route(x, p, cfg)
    out = jnp.zeros_like(x)
    for g in range(p["w1"].shape[0]):
        expert = cfg["expert_offset"] + g
        w_e = jnp.where(sel == expert, weight, 0.0).sum(-1)
        out = out + w_e[:, None] * swiglu(x, p["w1"][g], p["w3"][g],
                                          p["w2"][g], operand)
    return out


def layer(x, p, segment_ids, positions, cfg, operand):
    u = rms_norm(x, p["operator_norm"], cfg["norm_eps"])
    if "conv" in p:
        mixed = short_conv(u, p["conv"], operand)
    else:
        mixed = attention(u, p["self_attn"], segment_ids, positions, cfg,
                          operand)
    h = x + mixed
    u = rms_norm(h, p["ffn_norm"], cfg["norm_eps"])
    ffn = p["feed_forward"]
    if "router" in ffn:
        return h + expert_ffn(u, ffn, cfg, operand)
    return h + swiglu(u, ffn["w1"], ffn["w3"], ffn["w2"], operand)


def forward(params, tokens, segment_ids, positions, cfg, operand=identity):
    """One sequence: ``tokens`` / ``segment_ids`` / ``positions`` (S,)
    to logits (S, vocabulary held)."""
    x = params["embed_tokens"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(functools.partial(layer, cfg=cfg,
                                             operand=operand))(
            x, params[f"layers_{i}"], segment_ids, positions)
    x = rms_norm(x, params["embedding_norm"], cfg["norm_eps"])
    return product(x, params["embed_tokens"].T, operand)


def counted_positions(segment_ids):
    """(..., S) bool: the positions whose target lies in their own
    document."""
    s = segment_ids.shape[-1]
    return (jnp.roll(segment_ids, -1, axis=-1) == segment_ids) \
        & (jnp.arange(s) < s - 1)


def sequence_nll(params, tokens, segment_ids, positions, cfg, operand):
    """Sum of the counted positions' cross-entropies in one sequence."""
    logits = forward(params, tokens, segment_ids, positions, cfg, operand)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.roll(tokens, -1)[:, None], axis=-1)[:, 0]
    return jnp.where(counted_positions(segment_ids), logz - picked,
                     0.0).sum()


def loss_and_grads(params, batch, cfg, operand=identity):
    """Mean loss over the batch's counted positions and its gradient,
    accumulated a sequence at a time."""
    n = jnp.maximum(counted_positions(batch["segment_ids"]).sum(), 1)

    def one(carry, seq):
        total, grads = carry
        nll, g = jax.value_and_grad(sequence_nll)(
            params, seq["tokens"], seq["segment_ids"], seq["positions"],
            cfg, operand)
        return (total + nll, jax.tree.map(jnp.add, grads, g)), None

    zero = jax.tree.map(jnp.zeros_like, params)
    (total, grads), _ = jax.lax.scan(
        one, (jnp.zeros(()), zero),
        {k: batch[k] for k in ("tokens", "segment_ids", "positions")})
    return total / n, jax.tree.map(lambda g: g / n, grads)


def one_cycle_lr(step, lr, total_steps, pct_start=0.05):
    """PyTorch OneCycleLR, linear anneal, as ``optim.onecycle_schedule``
    states it: ``lr/25 -> lr`` over the first 5 %, then down to
    ``lr/25e4``."""
    warm = max(int(total_steps * pct_start), 1)
    up = lr / 25.0 + (lr - lr / 25.0) * jnp.minimum(step / warm, 1.0)
    frac = jnp.clip((step - warm) / (total_steps - warm), 0.0, 1.0)
    down = lr + (lr / 25.0 / 1e4 - lr) * frac
    return jnp.where(step < warm, up, down)


def train_step(params, opt, batch, step, *, cfg, lr, total_steps,
               wdecay, eps=1e-8, clip=1.0, b1=0.9, b2=0.999,
               operand=identity):
    """One step of the program's loop: gradient, global-norm clip, AdamW
    (decay on every leaf but the router's selection bias, which has no
    gradient and keeps its values), one-cycle rate. Returns the new
    parameters and moments, the loss and the clipped gradient."""
    loss, grads = loss_and_grads(params, batch, cfg, operand)
    norm = jnp.sqrt(sum(jnp.sum(g ** 2) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-30))
    grads = jax.tree.map(lambda g: g * scale, grads)
    t = step + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["nu"],
                      grads)
    rate = one_cycle_lr(step, lr, total_steps)

    def update(path, p, m, v):
        decay = 0.0 if path[-1].key == "expert_bias" else wdecay
        m_hat = m / (1 - b1 ** t)
        v_hat = v / (1 - b2 ** t)
        return p - rate * (m_hat / (jnp.sqrt(v_hat) + eps) + decay * p)

    new_params = jax.tree_util.tree_map_with_path(update, params, mu, nu)
    return new_params, {"mu": mu, "nu": nu}, loss, grads
