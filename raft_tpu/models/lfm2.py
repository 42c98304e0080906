"""LFM2-MoE decoder (LiquidAI ``lfm2_moe``): gated short convolutions
and grouped-query attention mixers, a dense SwiGLU in the leading layers
and a sigmoid-routed mixture of experts in the others.

Decoder layer ``l``: ``h = x + Mixer_l(RMSNorm(x))``,
``y = h + FFN_l(RMSNorm(h))``; one more RMSNorm after the last layer,
then the output head, tied to the embedding. No bias anywhere.

The model is one chip's share of an expert- and vocabulary-parallel
deployment (:class:`raft_tpu.config.LMConfig`): the router scores all
``num_experts`` and selects among all of them; the expert layer sorts
every token-expert assignment by expert and multiplies only the rows of
the experts held here (``ops/gmm.py``); what the absent experts would
add is left out and the partial result goes on. No token is dropped,
whatever the imbalance: the sorted buffer holds every assignment.

Precision (``mixed_precision``): bfloat16 matmul operands with float32
accumulation; parameters, router scores, norm statistics, softmax, the
residual stream and the logits stay float32.

Every stage is traced under a ``jax.named_scope``: ``embed``,
``short_conv``, ``attention``, ``dense_ffn``, ``moe_router``,
``moe_experts``, ``lm_head``.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from raft_tpu.config import LMConfig
from raft_tpu.models.lm_common import (_INIT, _dense, _dtype,
                                       _refuse_a_mesh_on_tpu, lm_head,
                                       rms_norm, rope, routed_experts,
                                       swiglu)
from raft_tpu.ops.attention import causal_attention


def _no_counters() -> dict:
    zero = jnp.zeros((), jnp.int32)
    return {"routed_here": zero, "expert_load_max": zero, "dropped": zero}


class ShortConv(nn.Module):
    """``[B, C, X] = split3(W_in u)``; ``z = B * X``; a depthwise causal
    convolution of ``conv_L_cache`` taps along the packed sequence (zeros
    before its start, no regard to document boundaries);
    ``out = W_out (C * conv(z))``."""
    cfg: LMConfig

    @nn.compact
    def __call__(self, u):
        cfg, d = self.cfg, self.cfg.hidden_size
        dtype = _dtype(cfg)
        w_in = self.param("in_proj", _INIT, (d, 3 * d))
        taps = self.param("conv", nn.initializers.normal(0.3),
                          (cfg.conv_L_cache, d))
        w_out = self.param("out_proj", _INIT, (d, d))
        with jax.named_scope("short_conv"):
            b, c, x = jnp.split(_dense(u, w_in, dtype), 3, axis=-1)
            z = b.astype(jnp.float32) * x.astype(jnp.float32)
            k = cfg.conv_L_cache
            padded = jnp.pad(z, ((0, 0), (k - 1, 0), (0, 0)))
            s = z.shape[1]
            conv = sum(taps[i] * padded[:, i:i + s] for i in range(k))
            return _dense(c.astype(jnp.float32) * conv, w_out, dtype)


class Attention(nn.Module):
    """Grouped-query attention with per-head q/k RMSNorm, RoPE at
    positions that restart with each document, and a causal mask within
    the document."""
    cfg: LMConfig

    @nn.compact
    def __call__(self, u, segment_ids, positions):
        cfg, d, hd = self.cfg, self.cfg.hidden_size, self.cfg.head_dim
        hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
        dtype = _dtype(cfg)
        w_q = self.param("q_proj", _INIT, (d, hq * hd))
        w_k = self.param("k_proj", _INIT, (d, hkv * hd))
        w_v = self.param("v_proj", _INIT, (d, hkv * hd))
        w_o = self.param("out_proj", _INIT, (hq * hd, d))
        q_w = self.param("q_layernorm", nn.initializers.ones, (hd,))
        k_w = self.param("k_layernorm", nn.initializers.ones, (hd,))
        with jax.named_scope("attention"):
            bsz, s, _ = u.shape
            q = _dense(u, w_q, dtype).reshape(bsz, s, hq, hd)
            k = _dense(u, w_k, dtype).reshape(bsz, s, hkv, hd)
            v = _dense(u, w_v, dtype).reshape(bsz, s, hkv, hd)
            q = rope(rms_norm(q, q_w, cfg.norm_eps), positions,
                     cfg.rope_theta)
            k = rope(rms_norm(k, k_w, cfg.norm_eps), positions,
                     cfg.rope_theta)
            q, k, v = (a.astype(dtype).transpose(0, 2, 1, 3)
                       for a in (q, k, v))      # heads first
            out = causal_attention(q, k, v, segment_ids, scale=hd ** -0.5)
            out = out.transpose(0, 2, 1, 3).reshape(bsz, s, hq * hd)
            return _dense(out, w_o, dtype)


class DenseFFN(nn.Module):
    cfg: LMConfig

    @nn.compact
    def __call__(self, x):
        cfg, d, f = self.cfg, self.cfg.hidden_size, \
            self.cfg.intermediate_size
        dtype = _dtype(cfg)
        w1 = self.param("w1", _INIT, (d, f))
        w3 = self.param("w3", _INIT, (d, f))
        w2 = self.param("w2", _INIT, (f, d))
        return swiglu(x, w1, w3, w2, dtype)


class ExpertFFN(nn.Module):
    """``s = sigmoid(W_g x)`` over all experts in float32;
    ``sel = topk(s + b)``; ``w = s[sel] / (sum(s[sel]) + 1e-6)`` times
    ``routed_scaling_factor``; ``out = sum_{e in sel, held} w_e E_e(x)``.
    Returns the layer's part and its counters."""
    cfg: LMConfig

    @nn.compact
    def __call__(self, x):
        cfg, d, f = self.cfg, self.cfg.hidden_size, \
            self.cfg.moe_intermediate_size
        n, k, held, off = (cfg.num_experts, cfg.num_experts_per_tok,
                           cfg.held, cfg.expert_offset)
        dtype = _dtype(cfg)
        w_g = self.param("router", _INIT, (d, n))
        bias = self.param("expert_bias", nn.initializers.zeros, (n,))
        w1 = self.param("w1", _INIT, (held, d, f))
        w3 = self.param("w3", _INIT, (held, d, f))
        w2 = self.param("w2", _INIT, (held, f, d))
        out, counters = routed_experts(
            x.reshape(-1, d), w_g, bias if cfg.use_expert_bias else None,
            w1, w3, w2, top_k=k, offset=off, norm_topk=cfg.norm_topk_prob,
            norm_eps=1e-6, scale=cfg.routed_scaling_factor, dtype=dtype)
        return out.astype(dtype).reshape(x.shape), counters


class DecoderLayer(nn.Module):
    cfg: LMConfig
    layer_type: str
    dense: bool

    @nn.compact
    def __call__(self, x, segment_ids, positions):
        cfg = self.cfg
        norm = lambda name: self.param(                    # noqa: E731
            name, nn.initializers.ones, (cfg.hidden_size,))
        u = rms_norm(x, norm("operator_norm"), cfg.norm_eps)
        if self.layer_type == "conv":
            mixed = ShortConv(cfg, name="conv")(u)
        else:
            mixed = Attention(cfg, name="self_attn")(u, segment_ids,
                                                     positions)
        h = x + mixed.astype(jnp.float32)
        u = rms_norm(h, norm("ffn_norm"), cfg.norm_eps)
        counters = _no_counters()
        if self.dense:
            out = DenseFFN(cfg, name="feed_forward")(u)
        else:
            out, counters = ExpertFFN(cfg, name="feed_forward")(u)
        return h + out.astype(jnp.float32), counters


class LFM2(nn.Module):
    """``tokens`` / ``segment_ids`` / ``positions`` (B, S) int32 ->
    ``(logits (B, S, vocab_held) float32, counters)``. The counters are
    summed (``routed_here``, ``dropped``) or maximised
    (``expert_load_max``) over the expert layers."""
    cfg: LMConfig

    @nn.compact
    def __call__(self, tokens, segment_ids, positions, train: bool = True):
        del train    # no dropout, no batch statistics
        _refuse_a_mesh_on_tpu()
        cfg = self.cfg
        dtype = _dtype(cfg)
        embed = self.param("embed_tokens", _INIT,
                           (cfg.vocab, cfg.hidden_size))
        with jax.named_scope("embed"):
            x = embed[tokens]
        # each layer is recomputed in the backward pass: its input is
        # what the forward keeps
        layer_cls = nn.remat(DecoderLayer)
        total = _no_counters()
        for i, kind in enumerate(cfg.layer_types):
            x, c = layer_cls(cfg, kind, i < cfg.num_dense_layers,
                             name=f"layers_{i}")(x, segment_ids, positions)
            total = {"routed_here": total["routed_here"] + c["routed_here"],
                     "expert_load_max": jnp.maximum(
                         total["expert_load_max"], c["expert_load_max"]),
                     "dropped": total["dropped"] + c["dropped"]}
        final = self.param("embedding_norm", nn.initializers.ones,
                           (cfg.hidden_size,))
        x = rms_norm(x, final, cfg.norm_eps)
        self.sow("intermediates", "final_hidden", x)
        return lm_head(x, embed, dtype), total
