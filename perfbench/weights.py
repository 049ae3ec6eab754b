"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights itself, so that the program under test
and the reference read the same numbers and neither made them.  Only the
layout (names, shapes, dtypes) is read from the program, abstractly, with
``jax.eval_shape``; every value comes from the seed here.  Each leaf is
drawn in the dtype it is served in, from its own ``fold_in`` stream:

* norm scales (``scale``) are ones;
* the token embedding has standard deviation 0.02;
* every other matrix is N(0, 1/fan_in), and the attention output
  projection ``wo`` is further scaled by 1/sqrt(layers) so the residual
  stream stays bounded through the stack.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, high bits included."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make(model, seed: int, n_layers: int):
    """The params pytree of ``model`` (a ``repro.models.model.Model``),
    filled from ``seed``."""
    layout = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    paths, treedef = jax.tree_util.tree_flatten_with_path(layout)

    def leaf(key, path, spec):
        name = getattr(path[-1], "key", str(path[-1]))
        if name == "scale":
            return jnp.ones(spec.shape, spec.dtype)
        if name == "embed":
            std = 0.02
        else:
            std = spec.shape[-2] ** -0.5
            if name == "wo":
                std /= n_layers ** 0.5
        return (jax.random.normal(key, spec.shape, spec.dtype)
                * jnp.asarray(std, spec.dtype))

    @jax.jit
    def build(key):
        return treedef.unflatten([
            leaf(jax.random.fold_in(key, i), path, spec)
            for i, (path, spec) in enumerate(paths)])

    return build(seed_key(seed))
