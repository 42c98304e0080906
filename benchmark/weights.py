"""Weights from ``--seed``, made on the device in one jitted call.

The benchmark owns the weights: it takes only the *shapes* of the
program's variable tree and fills every leaf from the seed, so the
program and the plain reference are handed the same arrays and neither
has made them. Kernels are He-normal over their fan-in; biases and
normalisation parameters are small and non-trivial, so that no term of
the forward pass is multiplied by exactly 0 or 1. The last convolution
of the flow head is scaled down so that 32 refinement iterations of an
untrained network move the flow by a few pixels instead of hundreds
(a trained RAFT's updates shrink as it converges; an untrained one's do
not).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: Scale on the flow head's output convolution (see module docstring).
FLOW_HEAD_GAIN = 0.05


def seed_key(seed: int):
    """A PRNG key from a whole number of up to 64 bits (the driver's
    seeds pass 2**31)."""
    seed = int(seed)
    words = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                     np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words))


def _leaf(key, path, shape, dtype):
    name = path[-1]
    normal = jax.random.normal(key, shape, jnp.float32)
    if name == "kernel":
        fan_in = int(np.prod(shape[:-1]))
        value = normal * np.sqrt(2.0 / fan_in)
        if path[-3:-1] == ("flow_head", "conv2"):
            value = value * FLOW_HEAD_GAIN
    elif name == "scale":
        value = 1.0 + 0.1 * normal
    elif name == "var":
        value = 1.0 + 0.1 * jnp.abs(normal)
    elif name == "bias" and path[-3:-1] == ("flow_head", "conv2"):
        value = FLOW_HEAD_GAIN * 0.1 * normal
    elif name in ("bias", "mean"):
        value = 0.1 * normal
    else:
        raise ValueError(f"no rule for leaf {'/'.join(path)}")
    return value.astype(dtype)


def make_variables(shapes, seed: int):
    """``shapes``: a pytree of ``jax.ShapeDtypeStruct`` (the program's
    variable tree). Returns the same tree filled from ``seed``, as
    device arrays made by one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [tuple(str(getattr(k, "key", k)) for k in path)
             for path, _ in flat]

    def build(key):
        return [_leaf(jax.random.fold_in(key, i), path, leaf.shape,
                      leaf.dtype)
                for i, (path, (_, leaf)) in enumerate(zip(paths, flat))]

    leaves = jax.jit(build)(seed_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def variable_shapes(model):
    """The shapes of a flax model's variable tree (``model.init`` traced,
    nothing computed)."""
    image = jax.ShapeDtypeStruct((1, 64, 64, 3), jnp.float32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.eval_shape(
        lambda r, a, b: model.init({"params": r, "dropout": r}, a, b,
                                   iters=1), key, image, image)
