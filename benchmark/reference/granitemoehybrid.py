"""Plain reference of the Granite-4.0-H decoder (ibm-granite
``granitemoehybrid``): forward pass, loss, gradients and the AdamW step
in straightforward ``jax.numpy``, float32, every product at
``Precision.HIGHEST``. No kernels, no chunked scan, no import from the
program.

Equations (HF ``modeling_granitemoehybrid.py``; departures are listed
under ``assumed`` in ``benchmark/configs/granite_4_0_h_micro.json``),
with ``r`` the ``residual_multiplier``:

* layer: ``h = u + r Mixer(RMSNorm(u))``, ``out = h + r MLP(RMSNorm(h))``;
  RMSNorm ``x * rsqrt(mean(x^2) + eps) * w``; ``x_0 =
  embedding_multiplier * embed(ids)``; a last RMSNorm, then the head,
  tied to the embedding, divided by ``logits_scaling``;
* MLP: ``W_out (silu(g) * v)`` with ``[g | v] = W_in x``;
* attention: 32 query and 8 key-value heads of 64, no rotary,
  ``softmax(attention_multiplier * q k^T)`` over the same document's
  keys at or before the query: the full masked softmax, a block of
  queries at a time;
* Mamba-2 mixer: ``[z | xBC | dt] = W_in u``; ``xBC = silu(conv(xBC) +
  b)``, a causal depthwise convolution of 4 taps that reads zeros for
  tokens of an earlier document; ``[x | B | C] = xBC``; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; **the literal
  per-token recurrence** ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T``
  per head (a 64 x 128 state), ``H_{t-1}`` set to nought at a
  document's first token, ``y_t = H_t C_t + D x_t``; ``y =
  RMSNorm_w(y * silu(z))`` over all ``d_inner`` channels; ``W_out y``;
* loss: mean next-token cross-entropy over the positions whose target
  lies in the same document, over the vocabulary held.

``operand`` is applied to both operands of every product the
configuration states in bfloat16, and to ``x``, ``B`` and ``C`` on
their way into the recurrence: ``identity`` for the reference,
``fp8_operand`` for the control.

So that the published widths fit one chip beside the reference's own
12.35 GB of float32 parameters, gradient and AdamW moments, the work is
blocked: one sequence of the batch at a time inside the differentiated
function (the backward pass adds each sequence's gradient into one
accumulator), one layer recomputed at a time, the time scan nested (32
outer steps of 256, the inner scan checkpointed: a flat scan's backward
pass would keep 8192 states of 2 MB a layer), attention over blocks of
512 queries, the loss over blocks of 1024 rows. None of it changes a
number beyond the order of float32 sums.

Two planted departures, for the controls only
(``benchmark/tools/ssm_control.py``): ``resets=False`` carries the state
and the convolution across document boundaries; ``keep_every=2`` leaves
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


def document_starts(segment_ids, resets=True):
    """(S,) bool: the token is its document's first (the sequence's
    first token always is)."""
    first = jnp.arange(segment_ids.shape[0]) == 0
    if not resets:
        return first
    return first | (segment_ids != jnp.roll(segment_ids, 1))


def short_conv(x, taps, bias, segment_ids, resets=True):
    """``out_t = b + sum_k taps[k] x_{t-K+1+k}``, a tap read as nought
    where its token is before the sequence or of an earlier document."""
    k, s = taps.shape[0], x.shape[0]
    at = jnp.arange(s)
    out = jnp.zeros_like(x) + bias
    for tap in range(k):
        source = at - (k - 1 - tap)
        inside = source >= 0
        if resets:
            inside &= segment_ids[jnp.maximum(source, 0)] == segment_ids
        out = out + taps[tap] * jnp.where(
            inside[:, None], x[jnp.maximum(source, 0)], 0.0)
    return out


def recurrence(x, dt, a, b, c, d_skip, starts, inner=256):
    """The state-space recurrence, a token at a time. ``x`` (S, H, P),
    ``dt`` (S, H), ``a`` (H,), ``b`` / ``c`` (S, N), ``d_skip`` (H,),
    ``starts`` (S,) -> ``y`` (S, H, P)."""
    s, h, p = x.shape
    n = b.shape[-1]
    inner = min(inner, s)

    def token(state, t):
        x_t, dt_t, b_t, c_t, start = t
        state = jnp.where(start, 0.0, state)
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, (state * c_t).sum(-1) + d_skip[:, None] * x_t

    @jax.checkpoint
    def block(state, ts):
        return jax.lax.scan(token, state, ts)

    blocked = jax.tree.map(
        lambda v: v.reshape(s // inner, inner, *v.shape[1:]),
        (x, dt, b, c, starts))
    _, y = jax.lax.scan(block, jnp.zeros((h, p, n)), blocked)
    return y.reshape(s, h, p)


def mamba(u, p, segment_ids, cfg, operand, resets=True):
    h, hd, n = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                cfg["mamba_d_state"])
    di = h * hd
    z, xbc, dt = jnp.split(product(u, p["in_proj"], operand),
                           [di, 2 * di + 2 * n], axis=-1)
    xbc = jax.nn.silu(short_conv(xbc, p["conv"], p["conv_bias"],
                                 segment_ids, resets))
    x, b, c = jnp.split(xbc, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(operand(x).reshape(-1, h, hd), dt, -jnp.exp(p["A_log"]),
                   operand(b), operand(c), p["D"],
                   document_starts(segment_ids, resets))
    y = rms_norm(y.reshape(-1, di) * jax.nn.silu(z), p["norm"],
                 cfg["rms_norm_eps"])
    return product(y, p["out_proj"], operand)


def attention(u, p, segment_ids, cfg, operand, q_block=512):
    s = u.shape[0]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // hq
    group = hq // hkv
    q = product(u, p["q_proj"], operand).reshape(s, hkv, group, hd)
    k = operand(product(u, p["k_proj"], operand).reshape(s, hkv, hd))
    v = operand(product(u, p["v_proj"], operand).reshape(s, hkv, hd))
    q_block = min(q_block, s)

    @jax.checkpoint
    def block(args):
        q_b, seg_b, at_b = args
        scores = jnp.einsum("qgrd,kgd->grqk", operand(q_b), k,
                            precision=HIGHEST) * cfg["attention_multiplier"]
        allowed = (seg_b[:, None] == segment_ids[None, :]) \
            & (at_b[:, None] >= jnp.arange(s)[None, :])
        probs = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", operand(probs), v,
                          precision=HIGHEST)

    blocks = s // q_block
    out = jax.lax.map(block, (
        q.reshape(blocks, q_block, hkv, group, hd),
        segment_ids.reshape(blocks, q_block),
        jnp.arange(s).reshape(blocks, q_block)))
    return product(out.reshape(s, hq * hd), p["out_proj"], operand)


def mlp(x, p, operand):
    gate, value = jnp.split(product(x, p["input_linear"], operand), 2,
                            axis=-1)
    return product(jax.nn.silu(gate) * value, p["output_linear"], operand)


def layer(x, p, segment_ids, cfg, operand, resets):
    r = cfg["residual_multiplier"]
    u = rms_norm(x, p["input_layernorm"], cfg["rms_norm_eps"])
    if "mamba" in p:
        mixed = mamba(u, p["mamba"], segment_ids, cfg, operand, resets)
    else:
        mixed = attention(u, p["self_attn"], segment_ids, cfg, operand)
    h = x + r * mixed
    u = rms_norm(h, p["post_attention_layernorm"], cfg["rms_norm_eps"])
    return h + r * mlp(u, p["shared_mlp"], operand)


def final_hidden(params, tokens, segment_ids, cfg, operand=identity,
                 resets=True):
    """One sequence: ``tokens`` / ``segment_ids`` (S,) to the hidden
    states after the last RMSNorm (S, hidden)."""
    x = cfg["embedding_multiplier"] * params["embed_tokens"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(functools.partial(
            layer, cfg=cfg, operand=operand, resets=resets))(
            x, params[f"layers_{i}"], segment_ids)
    return rms_norm(x, params["norm"], cfg["rms_norm_eps"])


def logits_of(hidden, params, cfg, operand=identity):
    return product(hidden, params["embed_tokens"].T, operand) \
        / cfg["logits_scaling"]


def forward(params, tokens, segment_ids, cfg, operand=identity):
    """One sequence's logits (S, vocabulary held)."""
    return logits_of(final_hidden(params, tokens, segment_ids, cfg,
                                  operand), params, cfg, operand)


def counted_positions(segment_ids, keep_every=1):
    """(..., S) bool: the positions whose target lies in their own
    document (every ``keep_every``-th of them)."""
    s = segment_ids.shape[-1]
    counted = (jnp.roll(segment_ids, -1, axis=-1) == segment_ids) \
        & (jnp.arange(s) < s - 1)
    return counted & (jnp.arange(s) % keep_every == 0)


def sequence_nll(params, tokens, segment_ids, cfg, operand, resets=True,
                 keep_every=1, row_block=1024):
    """Sum of the counted positions' cross-entropies in one sequence,
    the logits made a block of rows at a time."""
    hidden = final_hidden(params, tokens, segment_ids, cfg, operand, resets)
    s = tokens.shape[0]
    row_block = min(row_block, s)

    @jax.checkpoint
    def rows(total, block):
        hidden_b, target_b, counted_b = block
        logits = logits_of(hidden_b, params, cfg, operand)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, target_b[:, None],
                                     axis=-1)[:, 0]
        return total + jnp.where(counted_b, logz - picked, 0.0).sum(), None

    blocked = jax.tree.map(
        lambda v: v.reshape(s // row_block, row_block, *v.shape[1:]),
        (hidden, jnp.roll(tokens, -1),
         counted_positions(segment_ids, keep_every)))
    total, _ = jax.lax.scan(rows, jnp.zeros(()), blocked)
    return total


def loss_and_grads(params, batch, cfg, operand=identity, resets=True,
                   keep_every=1):
    """Mean loss over the batch's counted positions and its gradient;
    the sequences go through one at a time."""
    n = jnp.maximum(counted_positions(batch["segment_ids"],
                                      keep_every).sum(), 1)

    def mean_nll(params):
        @jax.checkpoint
        def one(total, seq):
            return total + sequence_nll(
                params, seq["tokens"], seq["segment_ids"], cfg, operand,
                resets, keep_every), None

        total, _ = jax.lax.scan(
            one, jnp.zeros(()),
            {k: batch[k] for k in ("tokens", "segment_ids")})
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


#: leaves AdamW's decay leaves alone: the recurrence's scalars and every
#: norm weight
NO_DECAY = ("A_log", "D", "dt_bias", "norm", "input_layernorm",
            "post_attention_layernorm")


def apply_update(params, opt, grads, step, *, lr, total_steps, wdecay,
                 eps=1e-8, clip=1.0, b1=0.9, b2=0.999):
    """Global-norm clip, AdamW (decay on the matrices, the embedding,
    the convolution's taps and bias), one-cycle rate. Returns the new
    parameters and moments and the clipped gradient."""
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
               operand=identity, resets=True, keep_every=1):
    """One step of the program's loop: ``loss_and_grads``, then
    ``apply_update``. Returns the new parameters and moments, the loss
    and the clipped gradient. (At the published widths the benchmark
    runs the two halves as two programs: as one, the gradient's
    accumulator and the gradient's output are two 3.1 GB buffers beside
    9.3 GB of parameters and moments, and the chip cannot load it.)"""
    loss, grads = loss_and_grads(params, batch, cfg, operand, resets,
                                 keep_every)
    new_params, opt, grads = apply_update(
        params, opt, grads, step, lr=lr, total_steps=total_steps,
        wdecay=wdecay, eps=eps, clip=clip, b1=b1, b2=b2)
    return new_params, opt, loss, grads
