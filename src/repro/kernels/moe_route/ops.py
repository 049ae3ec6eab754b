"""The routing's row movement for a layer that holds a share of the
experts: the dispatch and combine kernels (``kernel.py``).

The kernels have no derivative of their own, so each op carries a custom
VJP whose backward pass is the derivative of the XLA form (``ref.py``),
which computes the same function over every row of the pair buffer.

The caller picks the kernels, and only where they save work: where the
layer holds a share of the experts, so that most of the buffer is never
read, and where :func:`use_kernel` says they run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.moe_gmm import ops as gmm_ops
from repro.kernels.moe_route import ref
from repro.kernels.moe_route.kernel import (VMEM_LIMIT, moe_combine,
                                            moe_dispatch, vmem_bytes)

TILE_M = 128
VMEM_BUDGET = VMEM_LIMIT * 3 // 4       # room for the row tiles


def use_kernel(x) -> bool:
    """The kernels on a TPU, as the grouped matmul (``moe_gmm.ops``),
    for tokens ``x`` (T, d) that fit the VMEM the kernels hold them in."""
    return (gmm_ops.use_kernel()
            and vmem_bytes(*x.shape, x.dtype.itemsize) <= VMEM_BUDGET)


def _interpret(interpret):
    return default_interpret() if interpret is None else interpret


def _pad_rows(a):
    """``a`` with zero rows to a multiple of :data:`TILE_M`."""
    pad = -a.shape[0] % TILE_M
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) if pad else a


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def dispatch(x, order, count, k: int, interpret=None):
    """x: (T, d); order: (T*k,) int32, the token-expert pairs sorted with
    the held ones first; count: how many are held.  Returns the (T*k, d)
    pair buffer whose row r < ``count`` is ``x[order[r] // k]``; the
    rows past it are undefined."""
    m = order.shape[0]
    h = moe_dispatch(x, _pad_rows(order), count, k=k, tm=TILE_M,
                     interpret=_interpret(interpret))
    return h[:m] if h.shape[0] != m else h


def _dispatch_fwd(x, order, count, k, interpret):
    return dispatch(x, order, count, k, interpret), (x, order)


def _dispatch_bwd(k, interpret, res, g):
    x, order = res
    _, vjp = jax.vjp(lambda a: ref.dispatch_ref(a, order, k), x)
    return vjp(g)[0], None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def combine(out, gates, order, held, count, dtype, interpret=None):
    """out: (T*k, d), the pair buffer after the experts; gates: (T, k);
    order: (T*k,) as for :func:`dispatch`; held: (T*k,) bool, and count
    its sum.  Returns (T, d) in ``dtype``: per token the float32 sum of
    its held pairs' rows times their gates.  Only the first ``count``
    rows of ``out`` are read; the rest may hold anything, NaN included."""
    T, k = gates.shape
    order = _pad_rows(order)
    held_rows = jnp.arange(order.shape[0]) < count
    tok = jnp.where(held_rows, order // k, T).astype(jnp.int32)
    gate = gates.reshape(-1)[order]
    return moe_combine(_pad_rows(out), tok, gate, count, t=T, dtype=dtype,
                       tm=TILE_M, interpret=_interpret(interpret))


def _combine_fwd(out, gates, order, held, count, dtype, interpret):
    return (combine(out, gates, order, held, count, dtype, interpret),
            (out, gates, order, held))


def _combine_bwd(dtype, interpret, res, g):
    out, gates, order, held = res
    slot = ref.slots(order)
    _, vjp = jax.vjp(lambda o, w: ref.combine_ref(o, slot, held, w, dtype),
                     out, gates)
    d_out, d_gates = vjp(g)
    return d_out, d_gates, None, None, None


combine.defvjp(_combine_fwd, _combine_bwd)
