"""What the token models share (``models/lfm2.py``,
``models/granitemoehybrid.py``, ``models/afmoe.py``): the mixed policy's
dense product, the RMSNorm, the SwiGLU, half-rotation RoPE, the output
head, the routed experts of an expert-parallel share (router, the sort
of every assignment by expert, the product over the held experts alone:
``routed_experts``), and the refusal of a mesh on TPU. Each model's own
mixers and layer wiring stay in its module."""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from raft_tpu.ops.gmm import expert_gmm

_INIT = nn.initializers.normal(0.02)


def _refuse_a_mesh_on_tpu() -> None:
    """No kernel of the token models (``expert_gmm``, ``causal_attention``
    whole or windowed) has a ``shard_map`` wrapper, GSPMD cannot
    partition a Mosaic kernel, and the jnp twins do not fit a long
    sequence (8.6 GB of scores at 8192 tokens): traced on TPU over
    a mesh of more than one device a token model refuses, rather than
    choose a path that can only run out of memory."""
    if jax.default_backend() != "tpu":
        return
    from raft_tpu.parallel.spatial import current_spatial_kernel_mesh
    mesh = current_spatial_kernel_mesh()
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            f"a token model is traced over a {dict(mesh.shape)} mesh: "
            f"expert_gmm and causal_attention (whole or windowed) have "
            f"no shard_map wrapper yet; train it on one device")


def _dtype(cfg):
    """The matmul operands' dtype under the configuration's policy."""
    return jnp.bfloat16 if cfg.mixed_precision else jnp.float32


def _dense(x, w, dtype):
    """``x @ w``: operands in ``dtype``, float32 accumulation, result in
    ``dtype``."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32).astype(dtype)


def rms_norm(x, weight, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * weight


def rope(x, positions, theta: float):
    """Half-rotation RoPE on ``x`` (B, S, H, D) at ``positions`` (B, S):
    the pair of dimension ``i`` is ``i + D/2``."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    cos = jnp.cos(angle)[:, :, None, :]
    sin = jnp.sin(angle)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(x, w_gate, w_up, w_down, dtype, scope: str = "dense_ffn"):
    """``W_down (silu(W_gate x) * W_up x)``, the gate in float32."""
    with jax.named_scope(scope):
        gate = _dense(x, w_gate, dtype).astype(jnp.float32)
        up = _dense(x, w_up, dtype).astype(jnp.float32)
        return _dense(jax.nn.silu(gate) * up, w_down, dtype)


def lm_head(hidden, rows, dtype):
    """Logits in float32 over the vocabulary rows held (``rows``
    (vocabulary, hidden): the embedding's where the head is tied)."""
    with jax.named_scope("lm_head"):
        return jnp.dot(hidden.astype(dtype), rows.astype(dtype).T,
                       preferred_element_type=jnp.float32)


@jax.custom_vjp
def _permute(x, perm, inverse):
    """``x[perm]`` for a permutation whose inverse is known: the
    transpose is a gather too, not a scatter."""
    del inverse
    return x[perm]


def _permute_fwd(x, perm, inverse):
    return x[perm], (perm, inverse)


def _permute_bwd(res, g):
    perm, inverse = res
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def routed_experts(x, w_g, bias, w1, w3, w2, *, top_k: int, offset: int,
                   norm_topk: bool, norm_eps: float, scale: float, dtype):
    """One share of a sigmoid-routed expert layer. ``x`` (T, d);
    ``w_g`` (d, N) scores ALL ``N`` experts; ``bias`` (N,) is added for
    the selection only (``None``: none); ``w1`` / ``w3`` (G, d, f) and
    ``w2`` (G, f, d) are the ``G`` experts held here, ``offset`` on.

    ``s = sigmoid(W_g x)`` in float32; ``sel = topk(s + b)``;
    ``w = s[sel]``, over ``sum(s[sel]) + norm_eps`` where ``norm_topk``,
    times ``scale``; ``out = sum_{e in sel, held} w_e E_e(x)``. Every
    assignment is sorted by expert and only the held experts' rows are
    multiplied (``ops/gmm.py``); what the absent experts would add is
    left out and no assignment is dropped. Returns ``out`` (T, d) in
    float32 and the layer's counters."""
    n = w_g.shape[1]
    held = w1.shape[0]
    t, d = x.shape
    with jax.named_scope("moe_router"):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), w_g,
            precision=jax.lax.Precision.HIGHEST))
        ranked = scores
        if bias is not None:
            # a selection bias only: no gradient reaches it and its
            # update rule is not published, so it keeps its values
            ranked = scores + jax.lax.stop_gradient(bias)
        _, sel = jax.lax.top_k(ranked, top_k)
        weight = jnp.take_along_axis(scores, sel, axis=-1)
        if norm_topk:
            weight = weight / (weight.sum(-1, keepdims=True) + norm_eps)
        weight = weight * scale
        # every assignment, sorted by expert (stable: by token within)
        flat = sel.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        inverse = jnp.argsort(order)
        sizes = jnp.zeros((n,), jnp.int32).at[flat].add(1)
        here = sizes[offset:offset + held]
    with jax.named_scope("moe_experts"):
        rows = _permute(jnp.repeat(x.astype(dtype), top_k, axis=0), order,
                        inverse)
        gmm = lambda a, w: expert_gmm(                # noqa: E731
            a, w.astype(dtype), sizes, offset)
        gate = gmm(rows, w1).astype(jnp.float32)
        up = gmm(rows, w3).astype(jnp.float32)
        out = gmm((jax.nn.silu(gate) * up).astype(dtype), w2)
        out = _permute(out, inverse, order).reshape(t, top_k, d)
        out = (out.astype(jnp.float32) * weight[..., None]).sum(1)
    counters = {"routed_here": here.sum(),
                "expert_load_max": here.max(),
                "dropped": t * top_k - sizes.sum()}
    return out, counters
