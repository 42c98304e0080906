"""Blocked causal attention over packed documents, whole or through a
sliding window (``raft_attn``, ``raft_attn_window``).

``q`` (B, Hq, S, D), ``k`` / ``v`` (B, Hkv, S, D) with ``Hq`` a
multiple of ``Hkv`` (each key-value head serves ``Hq // Hkv`` query
heads), ``segment_ids`` (B, S): a query attends to the keys of its own
document at or before it and, with ``window``, at most ``window - 1``
positions before it. 8192 x 8192 scores for 32 heads are 8.6 GB a
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

With a window the kernel is the block-sparse one JAX ships beside it
(``jax.experimental.pallas.ops.tpu.splash_attention``): the window and
the causal edge are a static ``LocalMask`` from which it keeps, for each
block of queries, the list of key blocks that hold an allowed pair, so
the forward kernel and both backward ones run over those blocks alone
(five of a row's 32 at 16384 tokens, window 2048, blocks of 512); the
document mask comes from segment ids inside the blocks it runs. It is
the multi-query form, one key-value head and its group of query heads
at a time (``lax.map``, under which the calls keep their ``op_name``; a
``vmap`` over the heads loses it): no repeated copy of ``k`` or ``v``. That kernel has no scale
argument, so ``q`` is scaled on the way in. Traced under
``jax.named_scope(KERNEL_NAMES["attn_window"])``; its events are named
``splash_mqa_fwd...``, ``splash_mqa_dq...`` and ``splash_mqa_dkv...``.
A window that reaches the whole sequence masks nothing more than the
causal edge does, and takes the unwindowed path.

``causal_attention_reference`` is the jnp twin of both: the full masked
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


def causal_attention_reference(q, k, v, segment_ids, *, scale: float,
                               window: Optional[int] = None):
    """The jnp twin: full masked softmax in float32."""
    group = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    same = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
    back = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    allowed = back >= 0
    if window is not None:
        allowed &= back < window
    scores = jnp.where(same & allowed, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _windowed(q, k, v, segment_ids, *, scale: float, window: int,
              block: int, interpret: bool):
    """The block-sparse kernel over the window's blocks, one key-value
    head's group of query heads of one sequence at a time."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash, splash_attention_mask as masks)

    bsz, hq, s, hd = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    one = masks.LocalMask((s, s), window_size=(window - 1, 0), offset=0)
    sizes = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    kernel = splash.make_splash_mqa(
        masks.MultiHeadMask([one] * group), block_sizes=sizes,
        head_shards=1, q_seq_shards=1, interpret=interpret)
    if scale != 1.0:
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    q = q.reshape(bsz * hkv, group, s, hd)
    k, v = (a.reshape(bsz * hkv, s, hd) for a in (k, v))
    segment_ids = jnp.repeat(segment_ids, hkv, axis=0)

    def one_group(args):
        q1, k1, v1, seg1 = args
        return kernel(q1, k1, v1, splash.SegmentIds(seg1, seg1))

    with jax.named_scope(KERNEL_NAMES["attn_window"]):
        out = jax.lax.map(one_group, (q, k, v, segment_ids))
    return out.reshape(bsz, hq, s, hd)


def causal_attention(q, k, v, segment_ids, *, scale: float,
                     window: Optional[int] = None,
                     impl: Optional[str] = None,
                     block: int = DEFAULT_BLOCK):
    """``window``: a query sees at most ``window - 1`` positions back
    (``None``, or a window as long as the sequence: the whole causal
    document). ``impl`` ``"pallas"`` / ``"xla"`` forces a path; by
    default the kernel runs on TPU where the sequence tiles (a multiple
    of the block, itself a multiple of 128) and the twin elsewhere. The
    shipped unwindowed kernel has no ``interpret`` argument: off the
    TPU, ``"pallas"`` runs only inside
    ``pltpu.force_tpu_interpret_mode()``, held around the whole call and
    its compilation (the tests do); the windowed one interprets
    wherever the backend is not a TPU."""
    s = q.shape[2]
    if window is not None and window >= s:
        window = None
    block = min(block, s)
    tiles = s % block == 0 and block % 128 == 0
    if impl is None:
        impl = ("pallas" if jax.default_backend() == "tpu" and tiles
                else "xla")
    if impl == "xla":
        return causal_attention_reference(q, k, v, segment_ids,
                                          scale=scale, window=window)
    if not tiles:
        raise ValueError(f"causal_attention: sequence {s} does not tile "
                         f"into blocks of {block} (a multiple of 128)")
    vmem.preflight(block_parts(block, q.shape[3], q.dtype.itemsize),
                   f"causal_attention block {block}")
    if window is not None:
        return _windowed(q, k, v, segment_ids, scale=scale, window=window,
                         block=block,
                         interpret=jax.default_backend() != "tpu")
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
