"""Seeded inputs of a run: model weights and the key derivation.

The weights are made by the benchmark, not by the program: the program's
parameter layout (names and shapes, from ``jax.eval_shape`` of its
``init_params``) is filled with values drawn from ``--seed`` on the device,
in one jitted call. The plain references read the same arrays by name.
"""
from __future__ import annotations

import math
from typing import Any

MASK64 = (1 << 64) - 1


def base_key(seed: int, stream: int = 0):
    """A PRNG key for any whole ``seed`` (wider than 32 bits too) and an
    independent ``stream`` number."""
    import jax

    s = int(seed) & MASK64
    key = jax.random.PRNGKey(s & 0xFFFFFFFF)
    key = jax.random.fold_in(key, s >> 32)
    return jax.random.fold_in(key, stream)


def numpy_rng(seed: int, stream: int = 0):
    import numpy as np

    return np.random.default_rng([int(seed) & MASK64, stream])


def _leaf_value(key, path, shape, dtype):
    import jax
    import jax.numpy as jnp

    name = str(getattr(path[-1], "key", path[-1]))
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name == "bias":
        return jnp.zeros(shape, dtype)
    if name == "table":  # token embedding, tied to the output head
        return (0.02 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
            ).astype(dtype)


def make_params(layout: Any, seed: int):
    """Weights with the structure, shapes and dtypes of ``layout`` (a tree of
    ``jax.ShapeDtypeStruct``), drawn from ``seed`` in one jitted call:
    norm gains 1, biases 0, the embedding N(0, 0.02^2), every other matrix
    N(0, 1/fan_in)."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(layout)

    @jax.jit
    def build(key):
        leaves = [_leaf_value(jax.random.fold_in(key, i), path, s.shape,
                              s.dtype) for i, (path, s) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build(base_key(seed, stream=1))
