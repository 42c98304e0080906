"""Granite-4.0-H decoder (ibm-granite ``granitemoehybrid``): Mamba-2
state-space mixers nine layers in ten, grouped-query attention without
positions in the tenth, a dense SwiGLU in every layer, four scalars on
the residual path.

With ``u`` a layer's input and ``r`` the ``residual_multiplier``:
``h = u + r Mixer(RMSNorm(u))``, ``out = h + r MLP(RMSNorm(h))``;
``x_0 = embedding_multiplier * embed(ids)``; a last RMSNorm, then the
output head, tied to the embedding, its logits divided by
``logits_scaling``. No bias but the convolution's.

Mamba-2 mixer (``ops/ssd.py``): ``[z | xBC | dt] = W_in u``;
``xBC = silu(conv(xBC) + b)``, a causal depthwise convolution of
``mamba_d_conv`` taps that reads zeros in place of the previous
document's last tokens; ``[x | B | C] = xBC`` (one ``B`` and ``C`` for
all heads); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the
scan, its state dropped at each document's first token;
``y = RMSNorm_w(y * silu(z))`` over all ``d_inner`` channels (one
group, so the heads cannot be shared out); ``out = W_out y``.

Attention: no rotary (``position_embedding_type`` ``nope``), scores
scaled by ``attention_multiplier``, causal within the document.

Precision (``mixed_precision``): bfloat16 matmul operands with float32
accumulation; parameters, norm statistics, softmax, the residual
stream, the logits, ``dt``, ``A``, the log-decays and the carried state
stay float32.

Stages under ``jax.named_scope``: ``embed``, ``ssm_in_proj``,
``ssm_conv``, ``ssd_scan``, ``ssm_gated_norm``, ``ssm_out_proj``,
``attention``, ``dense_ffn``, ``lm_head``.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from raft_tpu.config import GraniteHybridConfig
from raft_tpu.models.lm_common import (_INIT, _dense, _dtype,
                                       _refuse_a_mesh_on_tpu, lm_head,
                                       rms_norm, swiglu)
from raft_tpu.ops.attention import causal_attention
from raft_tpu.ops.ssd import ssd_scan


def document_conv(x, taps, segment_ids):
    """Causal depthwise convolution along a packed sequence: ``out_t =
    sum_k taps[k] x_{t-K+1+k}`` over the taps whose token lies in
    ``t``'s document (zeros before it). ``x`` (B, S, C) float32,
    ``taps`` (K, C)."""
    k, s = taps.shape[0], x.shape[1]
    out = taps[k - 1] * x
    for back in range(1, k):
        earlier = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :s]
        theirs = jnp.pad(segment_ids, ((0, 0), (back, 0)),
                         constant_values=-1)[:, :s]
        out = out + taps[k - 1 - back] * jnp.where(
            (theirs == segment_ids)[..., None], earlier, 0.0)
    return out


class Mamba2Mixer(nn.Module):
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, u, segment_ids):
        cfg, d, di = self.cfg, self.cfg.hidden_size, self.cfg.d_inner
        h, p, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
        dtype = _dtype(cfg)
        conv_dim = di + 2 * cfg.mamba_n_groups * n
        w_in = self.param("in_proj", _INIT, (d, di + conv_dim + h))
        taps = self.param("conv", nn.initializers.normal(0.3),
                          (cfg.mamba_d_conv, conv_dim))
        conv_bias = self.param("conv_bias", nn.initializers.zeros,
                               (conv_dim,))
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(
                jnp.arange(1, shape[0] + 1, dtype=jnp.float32)), (h,))
        d_skip = self.param("D", nn.initializers.ones, (h,))
        dt_bias = self.param("dt_bias", nn.initializers.ones, (h,))
        norm = self.param("norm", nn.initializers.ones, (di,))
        w_out = self.param("out_proj", _INIT, (di, d))
        bsz, s, _ = u.shape
        with jax.named_scope("ssm_in_proj"):
            z, xbc, dt = jnp.split(_dense(u, w_in, dtype),
                                   [di, di + conv_dim], axis=-1)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        with jax.named_scope("ssm_conv"):
            xbc = jax.nn.silu(document_conv(
                xbc.astype(jnp.float32), taps, segment_ids) + conv_bias)
            x, b_in, c_in = jnp.split(xbc.astype(dtype), [di, di + n],
                                      axis=-1)
        y, resets = ssd_scan(
            x.reshape(bsz, s, h, p), dt, -jnp.exp(a_log),
            b_in[:, :, None], c_in[:, :, None], d_skip, segment_ids,
            chunk=cfg.mamba_chunk_size, dtype=dtype)
        with jax.named_scope("ssm_gated_norm"):
            y = y.reshape(bsz, s, di).astype(jnp.float32) \
                * jax.nn.silu(z.astype(jnp.float32))
            y = rms_norm(y, norm, cfg.rms_norm_eps)
        with jax.named_scope("ssm_out_proj"):
            out = _dense(y, w_out, dtype)
        chunks = bsz * (s // min(cfg.mamba_chunk_size, s))
        return out, {"ssm_resets": resets,
                     "ssd_chunks": jnp.asarray(chunks, jnp.int32)}


class Attention(nn.Module):
    """Grouped-query attention, no positions: the causal mask within a
    document is all the order it sees."""
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, u, segment_ids):
        cfg, d, hd = self.cfg, self.cfg.hidden_size, self.cfg.head_dim
        hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
        dtype = _dtype(cfg)
        w_q = self.param("q_proj", _INIT, (d, hq * hd))
        w_k = self.param("k_proj", _INIT, (d, hkv * hd))
        w_v = self.param("v_proj", _INIT, (d, hkv * hd))
        w_o = self.param("out_proj", _INIT, (hq * hd, d))
        with jax.named_scope("attention"):
            bsz, s, _ = u.shape
            q, k, v = (
                _dense(u, w, dtype).reshape(bsz, s, heads, hd)
                .transpose(0, 2, 1, 3)                   # heads first
                for w, heads in ((w_q, hq), (w_k, hkv), (w_v, hkv)))
            out = causal_attention(q, k, v, segment_ids,
                                   scale=cfg.attention_multiplier)
            out = out.transpose(0, 2, 1, 3).reshape(bsz, s, hq * hd)
            return _dense(out, w_o, dtype)


class SharedMLP(nn.Module):
    """``W_out (silu(g) * v)`` with ``[g | v] = W_in x``."""
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, x):
        d, f = self.cfg.hidden_size, self.cfg.shared_intermediate_size
        w_in = self.param("input_linear", _INIT, (d, 2 * f))
        w_out = self.param("output_linear", _INIT, (f, d))
        return swiglu(x, w_in[:, :f], w_in[:, f:], w_out,
                      _dtype(self.cfg))


def _no_counters() -> dict:
    zero = jnp.zeros((), jnp.int32)
    return {"ssm_resets": zero, "ssd_chunks": zero}


class DecoderLayer(nn.Module):
    cfg: GraniteHybridConfig
    layer_type: str

    @nn.compact
    def __call__(self, x, segment_ids):
        cfg, r = self.cfg, self.cfg.residual_multiplier
        norm = lambda name: self.param(                    # noqa: E731
            name, nn.initializers.ones, (cfg.hidden_size,))
        u = rms_norm(x, norm("input_layernorm"), cfg.rms_norm_eps)
        counters = _no_counters()
        if self.layer_type == "mamba":
            mixed, counters = Mamba2Mixer(cfg, name="mamba")(u, segment_ids)
        else:
            mixed = Attention(cfg, name="self_attn")(u, segment_ids)
        h = x + r * mixed.astype(jnp.float32)
        u = rms_norm(h, norm("post_attention_layernorm"), cfg.rms_norm_eps)
        out = SharedMLP(cfg, name="shared_mlp")(u)
        return h + r * out.astype(jnp.float32), counters


class GraniteMoeHybrid(nn.Module):
    """``tokens`` / ``segment_ids`` / ``positions`` (B, S) int32 ->
    ``(logits (B, S, vocab_held) float32, counters)``; ``positions`` are
    unused (no rotary). The counters are one state-space layer's: the
    document starts its reset masks saw (``ssm_resets``) and the chunks
    it scanned (``ssd_chunks``); every such layer sees the same."""
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, tokens, segment_ids, positions, train: bool = True):
        del positions, train    # no rotary; no dropout or batch statistics
        _refuse_a_mesh_on_tpu()
        cfg = self.cfg
        embed = self.param("embed_tokens", _INIT,
                           (cfg.vocab, cfg.hidden_size))
        with jax.named_scope("embed"):
            x = embed[tokens] * cfg.embedding_multiplier
        # each layer is recomputed in the backward pass: its input is
        # what the forward keeps
        layer_cls = nn.remat(DecoderLayer)
        counters = _no_counters()
        for i, kind in enumerate(cfg.layer_types):
            x, c = layer_cls(cfg, kind, name=f"layers_{i}")(x, segment_ids)
            counters = jax.tree.map(jnp.maximum, counters, c)
        final = self.param("norm", nn.initializers.ones, (cfg.hidden_size,))
        x = rms_norm(x, final, cfg.rms_norm_eps)
        return lm_head(x, embed, _dtype(cfg)) / cfg.logits_scaling, counters
