"""Random weights from a seed, made on the device in one jitted call.

The leaves follow the program's parameter tree (its structure comes from
``jax.eval_shape`` of the program's initializer; no weight of the program's
own is used).  Each leaf is drawn from its own key, so the reference can make
the very same values again from the seed: matrices N(0, 1/fan_in) with
fan_in the second-last dimension (the embedding: the last), norm gains
N(0, 0.1**2) around the model's implicit 1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp



def seed_key(seed: int):
    """A PRNG key from any seed up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _leaf(key, path, shape, dtype):
    name = jax.tree_util.keystr(path)
    if len(shape) == 1 or (len(shape) == 2 and "ln" in name.rsplit("[", 1)[-1]):
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    fan_in = shape[-1] if name.endswith("['embed']") else shape[-2]
    return (fan_in ** -0.5 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def weights_fn(abstract, dtype):
    """A traceable ``key -> tree`` shaped like ``abstract`` (a tree of
    ShapeDtypeStructs), every leaf in ``dtype``."""
    flat, tree = jax.tree_util.tree_flatten_with_path(abstract)

    def gen(key):
        leaves = [_leaf(jax.random.fold_in(key, i), path, x.shape, dtype)
                  for i, (path, x) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(tree, leaves)

    return gen


def make_weights(abstract, seed: int, dtype):
    """The weights of ``seed``, in one jitted call (the key is an argument,
    so every seed runs the same compiled program)."""
    gen = weights_fn(abstract, dtype)
    return jax.jit(gen)(seed_key(seed))
