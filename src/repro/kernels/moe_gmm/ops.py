"""The grouped matmul of the expert layer, on whatever platform runs it.

On a TPU it is the Pallas kernel (``kernel.moe_gmm``), whose work follows
the group sizes; elsewhere ``jax.lax.ragged_dot``.  The platform picks,
not the caller.

The kernel has no derivative of its own (Pallas cannot differentiate a
grid of traced length), so ``kernel_gmm`` carries a custom VJP: the
backward pass is the derivative of the ``ragged_dot`` form, which
computes the same function on the routed rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.moe_gmm.kernel import moe_gmm

TILE_M = 128


def use_kernel() -> bool:
    """The Pallas kernel on a TPU, ``ragged_dot`` elsewhere."""
    return jax.default_backend() == "tpu"


def gmm(lhs, rhs, group_sizes, up=None, *, stacked=None):
    """lhs: (M, K) rows sorted by group; rhs: (G, K, N); group_sizes: (G,)
    int32.  Returns (M, N) in ``lhs.dtype``: row r of group g is
    ``lhs[r] @ rhs[g]``, or with ``up`` (G, K, N) the SwiGLU
    ``silu(lhs[r] @ rhs[g]) * (lhs[r] @ up[g])``, in float32 before the
    cast.  Rows past ``sum(group_sizes)`` are undefined; the caller must
    not read them.

    ``stacked``, optional, is ``(rhs_all, up_all, layer)``: stacks
    (L, G, K, N) of every layer's weights whose slices ``[layer]`` are
    ``rhs`` and ``up`` (``up_all`` None without ``up``).  The kernel then
    reads the weights in the stack, so a layer scan copies no slice out;
    the gradient still goes to ``rhs`` and ``up``."""
    sizes = group_sizes.astype(jnp.int32)
    if not use_kernel():
        return _ragged(lhs, rhs, sizes, up)
    return kernel_gmm(lhs, rhs, sizes, up, stacked)


def _ragged(lhs, rhs, sizes, up):
    def dot(w):
        return jax.lax.ragged_dot(lhs, w, sizes,
                                  preferred_element_type=jnp.float32)
    out = dot(rhs) if up is None else jax.nn.silu(dot(rhs)) * dot(up)
    return out.astype(lhs.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def kernel_gmm(lhs, rhs, sizes, up=None, stacked=None, interpret=None):
    """``gmm`` through the Pallas kernel (in the interpreter where
    ``interpret`` resolves so, as on the CPU); rows padded to the tile."""
    if interpret is None:
        interpret = default_interpret()
    layer = None
    if stacked is not None:
        rhs, up_all, layer = stacked
        up = None if up is None else up_all
    m = lhs.shape[0]
    pad = -m % TILE_M
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    return moe_gmm(lhs, rhs, sizes, up, layer, tm=TILE_M,
                   interpret=interpret)[:m]


def _kernel_fwd(lhs, rhs, sizes, up, stacked, interpret):
    return (kernel_gmm(lhs, rhs, sizes, up, stacked, interpret),
            (lhs, rhs, sizes, up))


def _kernel_bwd(interpret, res, g):
    lhs, rhs, sizes, up = res
    _, vjp = jax.vjp(lambda a, w, u: _ragged(a, w, sizes, u), lhs, rhs, up)
    d_lhs, d_rhs, d_up = vjp(g)
    return d_lhs, d_rhs, None, d_up, None


kernel_gmm.defvjp(_kernel_fwd, _kernel_bwd)
