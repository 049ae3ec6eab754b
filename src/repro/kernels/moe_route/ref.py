"""The routing's row movement in XLA: a gather over every row of the
pair buffer and a masked gated sum over every pair (the form the
kernels replace, and their oracle)."""
from __future__ import annotations

import jax.numpy as jnp


def dispatch_ref(x, order, k: int):
    """(T, d) -> (T*k, d): row r is ``x[order[r] // k]``."""
    return x[order // k]


def slots(order):
    """The inverse permutation of ``order``: each pair's row."""
    n = order.shape[0]
    return jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)


def combine_ref(out, slot, held, gates, dtype):
    """out: (T*k, d) pair buffer; slot, held: (T*k,) each pair's row and
    whether its expert is held; gates: (T, k).  Returns (T, d) in
    ``dtype``: per token the float32 sum of its held pairs' rows times
    their gates (a pair not held is left out by a select)."""
    T, k = gates.shape
    back = jnp.where(held[:, None], out[slot], 0).astype(jnp.float32)
    y = (back.reshape(T, k, -1) * gates[..., None]).sum(1)
    return y.astype(dtype)
