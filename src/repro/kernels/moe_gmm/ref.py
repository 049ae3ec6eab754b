"""Pure-jnp oracle for the grouped matmul."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gmm_ref(lhs, rhs, group_sizes, up=None):
    """lhs: (M, K) rows sorted by group; rhs: (G, K, N); group_sizes: (G,)
    int32.  Row r of group g (rows ``sum(sizes[:g])`` up to
    ``sum(sizes[:g+1])``) becomes ``lhs[r] @ rhs[g]``; rows past
    ``sum(group_sizes)`` belong to no group and come back as zeros.
    With ``up`` (G, K, N) a row becomes the SwiGLU
    ``silu(lhs[r] @ rhs[g]) * (lhs[r] @ up[g])``.  Float32 accumulation,
    output in ``lhs.dtype``."""
    ends = jnp.cumsum(group_sizes)
    rows = jnp.arange(lhs.shape[0])
    group = jnp.searchsorted(ends, rows, side="right")      # G past the end

    def dot(w):
        return jnp.einsum("mk,gkn->mgn", lhs.astype(jnp.float32),
                          w.astype(jnp.float32))
    every = dot(rhs) if up is None else jax.nn.silu(dot(rhs)) * dot(up)
    picked = jnp.take_along_axis(
        every, jnp.minimum(group, rhs.shape[0] - 1)[:, None, None], 1)[:, 0]
    return jnp.where((group < rhs.shape[0])[:, None], picked,
                     0.0).astype(lhs.dtype)
