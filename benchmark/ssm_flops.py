"""Operations and bytes of the Granite-4.0-H training step, from shapes
and from the counts a run reports. A multiply-add is two operations;
only contractions count (norms, gates, the softmax's and the decays'
exponentials, the cumulative sums and the 32-step recurrence over chunk
states are under 1 %, so a share can only read low).

``cfg`` is the configuration file's ``model`` object (the published
widths and this chip's share).
"""

from __future__ import annotations

# attention and the head are the other token family's, from the same keys
from benchmark.lm_flops import (attention_flops_per_pair,  # noqa: F401
                                attention_projection_flops_per_token,
                                head_flops_per_token)


def d_inner(cfg: dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def mamba_projection_flops_per_token(cfg: dict) -> int:
    d, di = cfg["hidden_size"], d_inner(cfg)
    n, h = cfg["mamba_n_groups"] * cfg["mamba_d_state"], cfg["mamba_n_heads"]
    return 2 * d * (2 * di + 2 * n + h) + 2 * di * d    # in_proj, out_proj


def conv_flops_per_token(cfg: dict) -> int:
    channels = d_inner(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return 2 * cfg["mamba_d_conv"] * channels


def ssd_flops_per_chunk(cfg: dict) -> dict:
    """The scan's four products over one chunk of one sequence, all
    heads: ``C B^T`` (once: one group), ``(L * C B^T) x``, the chunk's
    end state, and ``C H``. The chunk is counted whole (the dual form's
    masked Q x Q product), whatever implements it."""
    q, n = cfg["mamba_chunk_size"], cfg["mamba_d_state"]
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    return {"cb": 2 * q * q * n * cfg["mamba_n_groups"],
            "intra": 2 * q * q * p * h,
            "states": 2 * q * p * n * h,
            "carried": 2 * q * n * p * h}


def ssd_bytes_per_token(cfg: dict, bytes_per_element: int = 2) -> dict:
    """What the scan of one layer must move for one token: forward it
    reads ``x``, ``dt``, ``B``, ``C`` and writes ``y``; backward it
    reads those and ``dy`` and writes the four gradients. ``dt`` is
    float32, the others ``bytes_per_element`` wide."""
    di = d_inner(cfg)
    bc = 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    inputs = (di + bc) * bytes_per_element + 4 * cfg["mamba_n_heads"]
    y = di * bytes_per_element
    return {"forward": inputs + y, "backward": inputs + y + inputs}


def mlp_flops_per_token(cfg: dict) -> int:
    return 2 * 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def forward_flops(cfg: dict, tokens: int, chunks_per_layer: int,
                  pairs_per_attention_layer: int) -> dict:
    """Required forward operations of ``tokens`` positions scanned as
    ``chunks_per_layer`` chunks a state-space layer."""
    kinds = cfg["layer_types"]
    mamba, attn = kinds.count("mamba"), kinds.count("attention")
    parts = {
        "ssm_projections": mamba * tokens
        * mamba_projection_flops_per_token(cfg),
        "ssm_conv": mamba * tokens * conv_flops_per_token(cfg),
        "ssd_scan": mamba * chunks_per_layer
        * sum(ssd_flops_per_chunk(cfg).values()),
        "attention_projections": attn * tokens
        * attention_projection_flops_per_token(cfg),
        "attention": attn * attention_flops_per_pair(cfg)
        * pairs_per_attention_layer,
        "mlp": len(kinds) * tokens * mlp_flops_per_token(cfg),
        "lm_head": tokens * head_flops_per_token(cfg)}
    parts["total"] = sum(parts.values())
    return parts


def train_step_flops(cfg: dict, tokens: int, chunks_per_layer: int,
                     pairs_per_attention_layer: int) -> dict:
    """Forward and backward: every contraction has two backward
    contractions of its own size. Recomputation is not counted."""
    forward = forward_flops(cfg, tokens, chunks_per_layer,
                            pairs_per_attention_layer)
    return {k: 3 * v for k, v in forward.items()}


def ssd_step(cfg: dict, tokens: int, chunks_per_layer: int) -> dict:
    """What a training step requires of the scans of all state-space
    layers, whatever implements them: the four products once forward
    and twice backward, and the bytes of ``ssd_bytes_per_token``."""
    layers = cfg["layer_types"].count("mamba")
    moved = ssd_bytes_per_token(cfg)
    return {"flops": 3 * layers * chunks_per_layer
            * sum(ssd_flops_per_chunk(cfg).values()),
            "bytes": layers * tokens * (moved["forward"]
                                        + moved["backward"])}
