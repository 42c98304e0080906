"""Trinity decoder (arcee-ai ``afmoe``): sliding-window and full
attention layers three to one, a sigmoid gate on the heads' output, four
norms a layer, a dense SwiGLU in the leading layers and, in the others,
sigmoid-routed experts beside a shared expert that every token takes.

Decoder layer: ``h = x + post_attention_layernorm(Attn(
input_layernorm(x)))``, ``y = h + post_mlp_layernorm(FFN(
pre_mlp_layernorm(h)))``. ``x_0 = sqrt(hidden) * embed(ids)``
(``mup_enabled``); one more RMSNorm after the last layer, then an
untied head. No bias anywhere.

Attention, both kinds: ``q``, ``k``, ``v`` and a gate ``g`` are
projections of the layer's input; RMSNorm with a learned weight over
each head's channels of ``q`` and of ``k``; scores scaled by
``head_dim ** -0.5``; ``out = W_o (attn(q, k, v) * sigmoid(g))``.
A ``sliding_attention`` layer rotates ``q`` and ``k`` (half-rotation
RoPE at positions that restart with each document) and a query sees its
own document's keys at most ``sliding_window - 1`` positions back; a
``full_attention`` layer applies **no positional encoding** and sees
the whole causal document (``ops/attention.py``, ``window=``).

Experts: ``models/lm_common.py::routed_experts`` (scores over all
``num_experts`` in float32, the ``num_experts_per_tok`` largest of
score plus ``expert_bias``, weights normalised over the chosen and
scaled by ``route_scale``), over the experts held here: one chip's
share of an expert-parallel deployment
(:class:`raft_tpu.config.AfmoeConfig`). The shared expert is
replicated: its whole output is added here, unweighted, and counted
once however many chips share the layer.

The head and the loss can run in blocks of positions
(``blocked_loss=True``: ``losses.blocked_token_cross_entropy``), so the
float32 logits of a whole step never exist at once.

Precision (``mixed_precision``): bfloat16 matmul operands with float32
accumulation; parameters, router scores, norm statistics, softmax, the
gate's sigmoid, the residual stream, logits and loss stay float32.

Stages under ``jax.named_scope``: ``embed``, ``attention_window``,
``attention_full``, ``attn_gate``, ``dense_ffn``, ``moe_router``,
``moe_experts``, ``moe_shared``, ``lm_head``, ``token_loss``.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from raft_tpu.config import AfmoeConfig
from raft_tpu.models.lm_common import (_INIT, _dense, _dtype,
                                       _refuse_a_mesh_on_tpu, rms_norm,
                                       rope, routed_experts, swiglu)
from raft_tpu.ops.attention import causal_attention

#: a layer's counters that add up over the expert layers; the third,
#: ``expert_load_max``, is their largest
_SUMMED = ("routed_here", "dropped")


def _no_counters() -> dict:
    zero = jnp.zeros((), jnp.int32)
    return {"routed_here": zero, "expert_load_max": zero, "dropped": zero}


def _gated(out, gate):
    """The heads' output times the sigmoid of the gate's projection,
    both float32, elementwise over every channel."""
    with jax.named_scope("attn_gate"):
        return out.astype(jnp.float32) * jax.nn.sigmoid(
            gate.astype(jnp.float32))


class Attention(nn.Module):
    """Grouped-query attention with per-head q/k RMSNorm and a sigmoid
    gate on the output; ``sliding`` layers are rotary and windowed, the
    others position-free and whole."""
    cfg: AfmoeConfig
    sliding: bool

    @nn.compact
    def __call__(self, u, segment_ids, positions):
        cfg, d, hd = self.cfg, self.cfg.hidden_size, self.cfg.head_dim
        hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
        dtype = _dtype(cfg)
        w_q = self.param("q_proj", _INIT, (d, hq * hd))
        w_k = self.param("k_proj", _INIT, (d, hkv * hd))
        w_v = self.param("v_proj", _INIT, (d, hkv * hd))
        w_g = self.param("gate_proj", _INIT, (d, hq * hd))
        w_o = self.param("o_proj", _INIT, (hq * hd, d))
        q_w = self.param("q_norm", nn.initializers.ones, (hd,))
        k_w = self.param("k_norm", nn.initializers.ones, (hd,))
        with jax.named_scope("attention_window" if self.sliding
                             else "attention_full"):
            bsz, s, _ = u.shape
            q = _dense(u, w_q, dtype).reshape(bsz, s, hq, hd)
            k = _dense(u, w_k, dtype).reshape(bsz, s, hkv, hd)
            v = _dense(u, w_v, dtype).reshape(bsz, s, hkv, hd)
            q = rms_norm(q, q_w, cfg.rms_norm_eps)
            k = rms_norm(k, k_w, cfg.rms_norm_eps)
            if self.sliding:
                q = rope(q, positions, cfg.rope_theta)
                k = rope(k, positions, cfg.rope_theta)
            # the scores' scale goes into q while it is float32: the
            # windowed kernel takes none
            q = q * hd ** -0.5
            q, k, v = (a.astype(dtype).transpose(0, 2, 1, 3)
                       for a in (q, k, v))      # heads first
            out = causal_attention(
                q, k, v, segment_ids, scale=1.0,
                window=cfg.sliding_window if self.sliding else None)
            out = out.transpose(0, 2, 1, 3).reshape(bsz, s, hq * hd)
            return _dense(_gated(out, _dense(u, w_g, dtype)), w_o, dtype)


class DenseFFN(nn.Module):
    cfg: AfmoeConfig
    width: int
    stage: str = "dense_ffn"

    @nn.compact
    def __call__(self, x):
        d, f = self.cfg.hidden_size, self.width
        w1 = self.param("w1", _INIT, (d, f))
        w3 = self.param("w3", _INIT, (d, f))
        w2 = self.param("w2", _INIT, (f, d))
        return swiglu(x, w1, w3, w2, _dtype(self.cfg), self.stage)


class MoE(nn.Module):
    """The held experts' part of the routed sum plus the shared
    expert's whole output; returns it and the layer's counters."""
    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, x):
        cfg, d, f = self.cfg, self.cfg.hidden_size, \
            self.cfg.moe_intermediate_size
        dtype = _dtype(cfg)
        w_g = self.param("router", _INIT, (d, cfg.num_experts))
        bias = self.param("expert_bias", nn.initializers.zeros,
                          (cfg.num_experts,))
        w1 = self.param("w1", _INIT, (cfg.held, d, f))
        w3 = self.param("w3", _INIT, (cfg.held, d, f))
        w2 = self.param("w2", _INIT, (cfg.held, f, d))
        out, counters = routed_experts(
            x.reshape(-1, d), w_g, bias, w1, w3, w2,
            top_k=cfg.num_experts_per_tok, offset=cfg.expert_offset,
            norm_topk=cfg.route_norm, norm_eps=1e-20,
            scale=cfg.route_scale, dtype=dtype)
        out = out.reshape(x.shape)
        if cfg.num_shared_experts:
            out = out + DenseFFN(cfg, f, "moe_shared", name="shared_expert")(
                x).astype(jnp.float32)
        return out.astype(dtype), counters


class DecoderLayer(nn.Module):
    cfg: AfmoeConfig
    layer_type: str
    dense: bool

    @nn.compact
    def __call__(self, x, segment_ids, positions):
        cfg = self.cfg
        norm = lambda name, a: rms_norm(a, self.param(     # noqa: E731
            name, nn.initializers.ones, (cfg.hidden_size,)),
            cfg.rms_norm_eps)
        mixed = Attention(
            cfg, self.layer_type == "sliding_attention", name="self_attn")(
            norm("input_layernorm", x), segment_ids, positions)
        h = x + norm("post_attention_layernorm", mixed)
        u = norm("pre_mlp_layernorm", h)
        counters = _no_counters()
        if self.dense:
            out = DenseFFN(cfg, cfg.intermediate_size, name="mlp")(u)
        else:
            out, counters = MoE(cfg, name="mlp")(u)
        return h + norm("post_mlp_layernorm", out), counters


class Afmoe(nn.Module):
    """``tokens`` / ``segment_ids`` / ``positions`` (B, S) int32 ->
    ``(logits (B, S, vocab_held) float32, counters)``, or with
    ``blocked_loss`` ``((loss, metrics), counters)`` with the head and
    the loss run in blocks of positions (``losses.LOSS_BLOCK``). The counters:
    ``routed_here`` and ``dropped`` summed and ``expert_load_max``
    maximised over the expert layers; ``window_pairs`` (the (query, key)
    pairs one sliding layer's mask allows: ``sum min(position + 1,
    sliding_window)``) and ``causal_pairs`` (one full layer's: ``sum
    (position + 1)``)."""
    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, tokens, segment_ids, positions, train: bool = True,
                 blocked_loss: bool = False):
        del train    # no dropout, no batch statistics
        _refuse_a_mesh_on_tpu()
        cfg = self.cfg
        dtype = _dtype(cfg)
        embed = self.param("embed_tokens", _INIT,
                           (cfg.vocab, cfg.hidden_size))
        head = self.param("lm_head", _INIT, (cfg.hidden_size, cfg.vocab))
        with jax.named_scope("embed"):
            x = embed[tokens]
            if cfg.mup_enabled:
                x = x * cfg.hidden_size ** 0.5
        # each layer is recomputed in the backward pass: its input is
        # what the forward keeps
        layer_cls = nn.remat(DecoderLayer)
        total = _no_counters()
        for i, kind in enumerate(cfg.layer_types):
            x, c = layer_cls(cfg, kind, i < cfg.num_dense_layers,
                             name=f"layers_{i}")(x, segment_ids, positions)
            total = {k: total[k] + c[k] if k in _SUMMED
                     else jnp.maximum(total[k], c[k]) for k in total}
        seen = positions + 1
        total["window_pairs"] = jnp.minimum(seen, cfg.sliding_window).sum()
        total["causal_pairs"] = seen.sum()
        final = self.param("norm", nn.initializers.ones, (cfg.hidden_size,))
        x = rms_norm(x, final, cfg.rms_norm_eps)
        if blocked_loss:
            from raft_tpu.losses import blocked_token_cross_entropy
            return blocked_token_cross_entropy(
                x, head, tokens, segment_ids, dtype=dtype), total
        with jax.named_scope("lm_head"):
            logits = jnp.dot(x.astype(dtype), head.astype(dtype),
                             preferred_element_type=jnp.float32)
        return logits, total
