"""Operations and bytes of the LFM2-MoE training step, from shapes and
from the counts a run reports. A multiply-add is two operations; only
contractions count (norms, gates, RoPE, the softmax's exponentials and
the convolution's 3 taps are under 1 %, so a share can only read low).

``cfg`` is the configuration file's ``model`` object (the published
widths and this chip's share).
"""

from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def short_conv_flops_per_token(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return 2 * d * 3 * d + 2 * d * d            # in_proj, out_proj


def attention_projection_flops_per_token(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], head_dim(cfg)
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    return 2 * d * q + 2 * 2 * d * kv + 2 * q * d


def attention_flops_per_pair(cfg: dict) -> int:
    """One (query, key) pair the mask allows: a dot of ``head_dim`` for
    the score and an update of ``head_dim`` for the value, every query
    head."""
    return cfg["num_attention_heads"] * 2 * 2 * head_dim(cfg)


def dense_ffn_flops_per_token(cfg: dict) -> int:
    return 2 * 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_flops_per_token(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["num_experts"]


def expert_flops_per_row(cfg: dict) -> int:
    """One token-expert assignment that fell on a held expert."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def head_flops_per_token(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_held"]


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def causal_pairs(segment_ids) -> int:
    """(query, key) pairs of one batch that the mask allows: a document
    of ``L`` tokens has ``L (L + 1) / 2``. ``segment_ids`` (B, S)
    numbers each sequence's documents in order."""
    import numpy as np
    total = 0
    for row in np.asarray(segment_ids):
        lengths = np.bincount(row).astype(np.int64)
        total += int((lengths * (lengths + 1) // 2).sum())
    return total


def forward_flops(cfg: dict, tokens: int, routed_rows: int,
                  pairs_per_attention_layer: int) -> dict:
    """Required forward operations of ``tokens`` positions, of which
    ``routed_rows`` token-expert assignments (summed over the expert
    layers) fell on held experts."""
    kinds = cfg["layer_types"]
    conv = kinds.count("conv") * short_conv_flops_per_token(cfg) * tokens
    attn_layers = kinds.count("full_attention")
    proj = attn_layers * attention_projection_flops_per_token(cfg) * tokens
    attn = attn_layers * attention_flops_per_pair(cfg) \
        * pairs_per_attention_layer
    dense = cfg["num_dense_layers"] * dense_ffn_flops_per_token(cfg) * tokens
    router = expert_layers(cfg) * router_flops_per_token(cfg) * tokens
    experts = expert_flops_per_row(cfg) * routed_rows
    head = head_flops_per_token(cfg) * tokens
    parts = {"short_conv": conv, "attention_projections": proj,
             "attention": attn, "dense_ffn": dense, "router": router,
             "experts": experts, "lm_head": head}
    parts["total"] = sum(parts.values())
    return parts


def train_step_flops(cfg: dict, tokens: int, routed_rows: int,
                     pairs_per_attention_layer: int) -> dict:
    """Forward and backward: every contraction has two backward
    contractions of its own size. Recomputation is not counted."""
    forward = forward_flops(cfg, tokens, routed_rows,
                            pairs_per_attention_layer)
    return {k: 3 * v for k, v in forward.items()}


def expert_gmm_step(cfg: dict, buffer_rows: int, routed_rows: int,
                    bytes_per_element: int = 2) -> dict:
    """The grouped products a training step requires of the expert
    layers, whatever implements them: three forward (``w1``, ``w3``:
    rows x hidden x width; ``w2``: rows x width x hidden) and for each
    its two backward products, nine a layer, each ``2 x rows x hidden x
    width`` operations over the rows routed here. Bytes: each product
    reads its two operands and writes its result once: the activations
    over the ``routed_rows`` that count and the held experts' matrices
    whole. ``buffer_rows`` (every assignment, held or not) is what a
    sorted buffer holds; it costs no required operation or byte."""
    del buffer_rows
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    layers, held = expert_layers(cfg), cfg["experts_held"]
    flops = 9 * 2 * routed_rows * d * f
    activations = routed_rows * (d + f) * bytes_per_element
    weights = layers * held * d * f * bytes_per_element
    return {"products": 9 * layers, "flops": flops,
            "bytes": 9 * (activations + weights)}
