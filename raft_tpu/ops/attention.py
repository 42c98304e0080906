"""Blocked causal attention over packed documents (``raft_attn``).

``q`` (B, Hq, S, D), ``k`` / ``v`` (B, Hkv, S, D) with ``Hq`` a
multiple of ``Hkv`` (each key-value head serves ``Hq // Hkv`` query
heads), ``segment_ids`` (B, S): a query attends to the keys of its own
document at or before it. 8192 x 8192 scores for 32 heads are 8.6 GB a
sequence in float32, so nothing here materialises them on the chip.

On TPU this is the flash attention JAX ships for Pallas
(``jax.experimental.pallas.ops.tpu.flash_attention``: one forward and
two backward kernels behind its custom VJP, causal blocks above the
diagonal skipped, the document mask from segment ids), one sequence at
a time (``lax.map``: its backward keeps (H, S, 128) float32 softmax
statistics, 0.4 GB a sequence), traced under
``jax.named_scope(KERNEL_NAMES["attn"])``. Its events in a device
trace are named ``flash_attention.N`` and ``flash_mha_bwd_dq...`` /
``flash_mha_bwd_dkv...``. That kernel wants as many key-value heads
as query heads, so ``k`` and ``v`` are repeated per group on the way in
(``jnp.repeat``, whose transpose sums the group's gradients).
``causal_attention_reference`` is the jnp twin: the full masked
softmax, for the CPU and for small sizes.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from raft_tpu.ops import vmem
from raft_tpu.ops.layout import KERNEL_NAMES

#: rows of queries and of keys in one grid step
DEFAULT_BLOCK = 512


def block_parts(block: int, head_dim: int, in_bytes: int) -> dict:
    """Named VMEM estimate of the largest launch (the dK/dV backward):
    double-buffered q, k, v, o, dO tiles, float32 score and probability
    tiles, the lane-broadcast softmax statistics and the accumulators."""
    tile = block * head_dim
    return {"qkv_o_do_tiles": 2 * 5 * tile * in_bytes,
            "scores_and_probabilities": 3 * block * block * 4,
            "softmax_statistics": 2 * 3 * block * 128 * 4,
            "accumulators": 2 * tile * 4}


def causal_attention_reference(q, k, v, segment_ids, *, scale: float):
    """The jnp twin: full masked softmax in float32."""
    group = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    same = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(same & causal, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def causal_attention(q, k, v, segment_ids, *, scale: float,
                     impl: Optional[str] = None,
                     block: int = DEFAULT_BLOCK):
    """``impl`` ``"pallas"`` / ``"xla"`` forces a path; by default the
    kernel runs on TPU where the sequence tiles (a multiple of the
    block, itself a multiple of 128) and the twin elsewhere. The shipped
    kernel has no ``interpret`` argument: off the TPU, ``"pallas"`` runs
    only inside ``pltpu.force_tpu_interpret_mode()``, held around the
    whole call and its compilation (the tests do)."""
    s = q.shape[2]
    block = min(block, s)
    tiles = s % block == 0 and block % 128 == 0
    if impl is None:
        impl = ("pallas" if jax.default_backend() == "tpu" and tiles
                else "xla")
    if impl == "xla":
        return causal_attention_reference(q, k, v, segment_ids,
                                          scale=scale)
    if not tiles:
        raise ValueError(f"causal_attention: sequence {s} does not tile "
                         f"into blocks of {block} (a multiple of 128)")
    vmem.preflight(block_parts(block, q.shape[3], q.dtype.itemsize),
                   f"causal_attention block {block}")
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    sizes = fa.BlockSizes(
        block_q=block, block_k_major=block, block_k=block, block_b=1,
        block_q_major_dkv=block, block_k_major_dkv=block,
        block_k_dkv=block, block_q_dkv=block,
        block_k_major_dq=block, block_k_dq=block, block_q_dq=block)
    group = q.shape[1] // k.shape[1]

    def one_sequence(args):
        q1, k1, v1, seg1 = (a[None] for a in args)
        k1 = jnp.repeat(k1, group, axis=1)
        v1 = jnp.repeat(v1, group, axis=1)
        return fa.flash_attention(
            q1, k1, v1, segment_ids=fa.SegmentIds(seg1, seg1),
            causal=True, sm_scale=scale, block_sizes=sizes)[0]

    with jax.named_scope(KERNEL_NAMES["attn"]):
        return jax.lax.map(one_sequence, (q, k, v, segment_ids))
