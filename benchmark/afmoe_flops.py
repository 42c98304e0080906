"""Operations and bytes of the Trinity (``afmoe``) training step, from
shapes and from the counts a run reports. A multiply-add is two
operations; only contractions count (norms, gates, RoPE, the softmax's
exponentials and the router's top-k are under 1 %, so a share can only
read low).

``cfg`` is the configuration file's ``model`` object (the published
widths and this chip's share).
"""

from __future__ import annotations

# the experts' grouped products are the other expert family's, from the
# same keys
from benchmark.lm_flops import (dense_ffn_flops_per_token,  # noqa: F401
                                expert_flops_per_row, expert_gmm_step,
                                expert_layers, head_flops_per_token,
                                router_flops_per_token)


def attention_projection_flops_per_token(cfg: dict) -> int:
    """``q``, ``k``, ``v``, the gate and the output projection of one
    layer, either kind."""
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 2 * d * (2 * q + 2 * kv) + 2 * q * d


def attention_flops_per_pair(cfg: dict) -> int:
    """One (query, key) pair the mask allows: a dot of ``head_dim`` for
    the score and an update of ``head_dim`` for the value, every query
    head."""
    return cfg["num_attention_heads"] * 2 * 2 * cfg["head_dim"]


def shared_expert_flops_per_token(cfg: dict) -> int:
    return cfg["num_shared_experts"] * 2 * 3 * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"]


def forward_flops(cfg: dict, tokens: int, routed_rows: int,
                  window_pairs: int, causal_pairs: int) -> dict:
    """Required forward operations of ``tokens`` positions, of which
    ``routed_rows`` token-expert assignments (summed over the expert
    layers) fell on held experts; ``window_pairs`` / ``causal_pairs``
    are the pairs one sliding / one full layer's mask allows."""
    kinds = cfg["layer_types"]
    sliding, full = (kinds.count("sliding_attention"),
                     kinds.count("full_attention"))
    moe = expert_layers(cfg)
    parts = {
        "attention_projections": len(kinds) * tokens
        * attention_projection_flops_per_token(cfg),
        "attention_window": sliding * attention_flops_per_pair(cfg)
        * window_pairs,
        "attention_full": full * attention_flops_per_pair(cfg)
        * causal_pairs,
        "dense_ffn": cfg["num_dense_layers"] * tokens
        * dense_ffn_flops_per_token(cfg),
        "router": moe * tokens * router_flops_per_token(cfg),
        "experts": expert_flops_per_row(cfg) * routed_rows,
        "shared_expert": moe * tokens * shared_expert_flops_per_token(cfg),
        "lm_head": tokens * head_flops_per_token(cfg)}
    parts["total"] = sum(parts.values())
    return parts


def train_step_flops(cfg: dict, tokens: int, routed_rows: int,
                     window_pairs: int, causal_pairs: int) -> dict:
    """Forward and backward: every contraction has two backward
    contractions of its own size. Recomputation is not counted."""
    forward = forward_flops(cfg, tokens, routed_rows, window_pairs,
                            causal_pairs)
    return {k: 3 * v for k, v in forward.items()}


def attn_window_step(cfg: dict, tokens: int, window_pairs: int,
                     bytes_per_element: int = 2) -> dict:
    """What a training step requires of the sliding layers' attention,
    whatever implements it: over the pairs the window, the document and
    the causal edge allow, the two forward products (scores, values)
    and their four backward ones (the scores made again in the backward
    pass, and the layer's recomputed forward, are executed, not
    required). Bytes: forward reads ``q``, ``k``, ``v`` and writes the
    output; backward reads those four and the output's gradient and
    writes three gradients."""
    layers = cfg["layer_types"].count("sliding_attention")
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    forward = 2 * q + 2 * kv
    backward = 3 * q + 2 * kv + (q + 2 * kv)
    return {"flops": 3 * layers * attention_flops_per_pair(cfg)
            * window_pairs,
            "bytes": layers * tokens * (forward + backward)
            * bytes_per_element}
