"""Chunked state-space-duality scan of Mamba-2 over packed documents
(``ssd_scan``).

Per head, with ``a_t = dt_t A`` (negative) and a state ``H`` of
(head_dim, d_state)::

    H_t = exp(a_t) H_{t-1} + dt_t x_t B_t^T        y_t = H_t C_t + D x_t

and a token that starts a document sees no earlier state: ``H_{t-1}``
is dropped there (the ``seq_idx`` semantics of the published kernels).

The sequence is cut into chunks of ``chunk`` tokens. With ``cs`` the
cumulative sum of ``a`` inside a chunk:

* within a chunk, ``y_i += sum_j L[i, j] (C_i . B_j) dt_j x_j`` where
  ``L[i, j] = exp(cs_i - cs_j)`` for ``j <= i`` in the same document,
  else 0; ``C B^T`` is computed once a chunk (one group serves all
  heads);
* each chunk's end state ``sum_j exp(cs_last - cs_j) dt_j x_j B_j^T``
  over the tokens of the chunk's last document;
* a recurrence over the chunks' states (``lax.scan``, all heads at
  once, float32 on the VPU), the carried state dropped where a chunk's
  last token is of another document than the previous chunk's;
* ``y_i += exp(cs_i) C_i . H_in`` for the rows of a chunk that lie in
  the document its first token continues.

Every mask is a comparison of ``segment_ids``; no log-decay is pushed
to a large negative number (the differences of cumulative sums would
lose their digits). ``dt``, ``A``, the cumulative sums, their
exponentials and the carried state are float32; the four products take
operands in ``dtype`` and accumulate in float32.

The (chunk, chunk) matrices of all heads would be 0.5 GB a sequence in
float32, several times over in the backward pass: heads go through in
blocks (``lax.map``), each block checkpointed, so the backward pass
keeps a block's inputs and recomputes its matrices.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


class ResetMasks(NamedTuple):
    #: (B, chunks, Q, Q): row ``i`` reads column ``j``: ``j <= i`` and
    #: the same document
    within: jnp.ndarray
    #: (B, chunks, Q): the token lies in the chunk's last document, so
    #: it reaches the chunk's end state
    to_end: jnp.ndarray
    #: (B, chunks, Q): the token lies in the document of the token just
    #: before the chunk, so the carried-in state reaches it
    from_start: jnp.ndarray
    #: (B, chunks): the carried-in state passes through the whole chunk
    passes: jnp.ndarray
    #: int32: the document starts the masks cut the state at (a
    #: sequence's first token is not one: there is nothing to drop)
    resets: jnp.ndarray


def reset_masks(segment_ids, chunk: int) -> ResetMasks:
    b, s = segment_ids.shape
    seg = segment_ids.reshape(b, s // chunk, chunk)
    last = seg[:, :, -1]
    # ids number a sequence's documents from 0: -1 is no document's
    before = jnp.concatenate(
        [jnp.full((b, 1), -1, seg.dtype), last[:, :-1]], axis=1)
    at = jnp.arange(chunk)
    within = (seg[..., :, None] == seg[..., None, :]) \
        & (at[:, None] >= at[None, :])
    from_start = seg == before[..., None]
    cut_inside = ~jnp.diagonal(within, offset=-1, axis1=-2, axis2=-1)
    cut_at_edge = ~from_start[:, 1:, 0]
    resets = cut_inside.sum() + cut_at_edge.sum()
    return ResetMasks(within, seg == last[..., None], from_start,
                      last == before, resets.astype(jnp.int32))


def _chunk_states(block, shared, dtype):
    """One block of heads: each chunk's end state (B, chunks, hb, P, N)."""
    xb, dtb, csb = block
    b_c, to_end = shared
    weight = jnp.where(to_end, jnp.exp(csb[..., -1:] - csb), 0.0) * dtb
    xw = (xb.astype(F32) * weight[..., None]).astype(dtype)
    return jnp.einsum("hbcjp,bcjn->bchpn", xw, b_c,
                      preferred_element_type=F32)


def _chunk_outputs(block, shared, dtype):
    """One block of heads: ``y`` (hb, B, chunks, Q, P) in ``dtype``."""
    xb, dtb, csb, h_in, d_skip = block
    cb, c_c, within, from_start = shared
    diff = csb[..., :, None] - csb[..., None, :]
    # the inner select keeps exp's argument at or below 0 where the
    # outer one would discard an overflow (and its gradient a NaN)
    decay = jnp.where(within, jnp.exp(jnp.where(within, diff, 0.0)), 0.0)
    xf = xb.astype(F32)
    y = jnp.einsum("hbcij,hbcjp->hbcip", (decay * cb).astype(dtype),
                   (xf * dtb[..., None]).astype(dtype),
                   preferred_element_type=F32)
    carried = jnp.einsum("bcin,bchpn->hbcip", c_c, h_in.astype(dtype),
                         preferred_element_type=F32)
    y = y + jnp.where(from_start, jnp.exp(csb), 0.0)[..., None] * carried
    return (y + d_skip[:, None, None, None, None] * xf).astype(dtype)


def ssd_scan(x, dt, A, B, C, D, segment_ids, *, chunk: int = 256,
             head_block: int = 8, dtype=jnp.bfloat16):
    """``x`` (B, S, H, P), ``dt`` (B, S, H) after its softplus, ``A``
    (H,) negative, ``B`` / ``C`` (B, S, 1, N), ``D`` (H,),
    ``segment_ids`` (B, S) -> ``(y (B, S, H, P) in dtype, resets)``;
    ``S`` a multiple of ``chunk`` (or shorter than one), ``resets`` the
    document starts the masks saw."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"ssd_scan: sequence {s} is not a multiple of "
                         f"the chunk {chunk}")
    nc = s // chunk
    hb = max(k for k in range(1, min(head_block, h) + 1) if h % k == 0)
    nb = h // hb
    with jax.named_scope("ssd_scan"):
        masks = reset_masks(segment_ids, chunk)
        b_c = B.reshape(b, nc, chunk, n).astype(dtype)
        c_c = C.reshape(b, nc, chunk, n).astype(dtype)
        cb = jnp.where(masks.within, jnp.einsum(
            "bcin,bcjn->bcij", c_c, b_c, preferred_element_type=F32), 0.0)
        # heads first, in blocks: (blocks, hb, B, chunks, Q[, P])
        xh = x.astype(dtype).reshape(b, nc, chunk, nb, hb, p) \
            .transpose(3, 4, 0, 1, 2, 5)
        dth = dt.astype(F32).reshape(b, nc, chunk, nb, hb) \
            .transpose(3, 4, 0, 1, 2)
        cs = jnp.cumsum(dth * A.astype(F32).reshape(nb, hb, 1, 1, 1),
                        axis=-1)

        def states(block):
            return jax.checkpoint(_chunk_states, static_argnums=2)(
                block, (b_c, masks.to_end), dtype)

        ends = jax.lax.map(states, (xh, dth, cs))  # (nb, B, nc, hb, P, N)
        # (nc, nb, B, hb): what a chunk leaves of the state it was handed
        gain = jnp.where(masks.passes, jnp.exp(cs[..., -1]), 0.0) \
            .transpose(3, 0, 2, 1)

        def carry_on(state, chunk_in):
            gain_c, end_c = chunk_in
            return gain_c[..., None, None] * state + end_c, state

        _, h_in = jax.lax.scan(
            carry_on, jnp.zeros((nb, b, hb, p, n), F32),
            (gain, jnp.moveaxis(ends, 2, 0)))
        h_in = jnp.moveaxis(h_in, 0, 2)

        def outputs(block):
            return jax.checkpoint(_chunk_outputs, static_argnums=2)(
                block, (cb, c_c, masks.within, masks.from_start), dtype)

        y = jax.lax.map(outputs, (xh, dth, cs, h_in,
                                  D.astype(F32).reshape(nb, hb)))
        y = y.transpose(2, 3, 4, 0, 1, 5).reshape(b, s, h, p)
    return y, masks.resets
