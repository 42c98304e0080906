"""What the token models share (``models/lfm2.py``,
``models/granitemoehybrid.py``): the mixed policy's dense product, the
RMSNorm, the SwiGLU, the tied output head, and the refusal of a mesh on
TPU. Each model's own mixers and layer wiring stay in its module."""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

_INIT = nn.initializers.normal(0.02)


def _refuse_a_mesh_on_tpu() -> None:
    """No kernel of the token models has a ``shard_map`` wrapper, GSPMD
    cannot partition a Mosaic kernel, and the jnp twins do not fit a
    long sequence (8.6 GB of scores at 8192 tokens): traced on TPU over
    a mesh of more than one device a token model refuses, rather than
    choose a path that can only run out of memory."""
    if jax.default_backend() != "tpu":
        return
    from raft_tpu.parallel.spatial import current_spatial_kernel_mesh
    mesh = current_spatial_kernel_mesh()
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            f"a token model is traced over a {dict(mesh.shape)} mesh: "
            f"expert_gmm and causal_attention have no shard_map wrapper "
            f"yet; train it on one device")


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


def swiglu(x, w_gate, w_up, w_down, dtype):
    """``W_down (silu(W_gate x) * W_up x)``, the gate in float32."""
    with jax.named_scope("dense_ffn"):
        gate = _dense(x, w_gate, dtype).astype(jnp.float32)
        up = _dense(x, w_up, dtype).astype(jnp.float32)
        return _dense(jax.nn.silu(gate) * up, w_down, dtype)


def lm_head(hidden, rows, dtype):
    """Logits in float32 over the vocabulary rows held (the embedding's,
    tied)."""
    with jax.named_scope("lm_head"):
        return jnp.dot(hidden.astype(dtype), rows.astype(dtype).T,
                       preferred_element_type=jnp.float32)
