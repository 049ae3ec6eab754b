"""Grouped matmul over expert-sorted rows — Pallas kernel.

The rows of ``lhs`` are sorted by group (expert); group g's rows are
multiplied by ``rhs[g]``.  The buffer holds as many rows as the worst
case of routing needs, but the grid visits only the row tiles that hold
routed rows: its length is a traced number, computed from the group
sizes by megablox's ``make_group_metadata`` (JAX's grouped-matmul
kernels), which also maps each grid step to a (group, row tile) pair.
A tile that straddles two groups is visited once per group, and each
visit stores only its own group's rows.  Tiles past the last routed row
are never visited, so those rows of the output are left unwritten.
Given a second weight (``up``) the kernel computes the expert layer's
SwiGLU ``silu(x @ gate) * (x @ up)`` in one pass.

The whole (K, N) weight of a group is one block and the grid's steps
over one group's tiles are consecutive, so each held expert's weight
crosses from HBM once per call however many tiles its rows fill: at a
few hundred rows per expert the layer is bound by those bytes.

The weights may be a stack of every layer's, (L, G, K, N), with the
layer's index among the scalar operands: a layer scan then hands the
kernel its stacked parameters as they are, where a slice of them would
be copied out for each layer (a custom call cannot read a slice in
place, as a fused matmul does).

grid = (active row tiles,); the op is named ``moe_gmm``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

NAME = "moe_gmm"
# two double-buffered (K, N) weight blocks of a fused SwiGLU at SDAR's
# widths (2048 x 768 bf16) take 12 MiB, near the 16 MiB default scope
VMEM_LIMIT = 48 * 2**20


def _gmm_kernel(offsets, group_ids, tile_ids, layer, lhs_ref, rhs_ref,
                *refs, tm: int, swiglu: bool):
    i = pl.program_id(0)
    acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                  preferred_element_type=jnp.float32)
    if swiglu:
        up_ref, out_ref = refs
        acc = jax.nn.silu(acc) * jnp.dot(lhs_ref[...], up_ref[...],
                                         preferred_element_type=jnp.float32)
    else:
        (out_ref,) = refs
    g = group_ids[i]
    rows = tile_ids[i] * tm + jax.lax.broadcasted_iota(jnp.int32,
                                                        acc.shape, 0)
    mine = (rows >= offsets[g]) & (rows < offsets[g + 1])
    out_ref[...] = jnp.where(mine, acc, out_ref[...].astype(jnp.float32)
                             ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def moe_gmm(lhs, rhs, group_sizes, up=None, layer=None, *, tm: int = 128,
            interpret: bool = False):
    """lhs: (M, K) with M a multiple of ``tm``; rhs: (G, K, N), or given
    ``layer`` (an int32 scalar) a stack (L, G, K, N) of which the kernel
    reads ``rhs[layer]`` in place; group_sizes: (G,) int32 summing to at
    most M.  Returns (M, N) in ``lhs.dtype``: ``lhs @ rhs[g]`` for the
    rows of group g, or, given ``up`` (shaped as ``rhs``), the SwiGLU
    ``silu(lhs @ rhs[g]) * (lhs @ up[g])`` computed in float32.  Rows
    past ``sum(group_sizes)`` are undefined."""
    if layer is None:                    # one layer: a stack of one
        rhs, up, layer = rhs[None], None if up is None else up[None], 0
    m, k = lhs.shape
    _, g, _, n = rhs.shape
    if m % tm:
        raise ValueError(f"rows {m} must divide the row tile {tm}; "
                         "use ops.gmm, which pads")
    (offsets, group_ids, tile_ids), tiles = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=g,
        visit_empty_groups=False)
    weight = pl.BlockSpec((None, None, k, n),
                          lambda i, o, gi, ti, l: (l[0], gi[i], 0, 0))
    weights = (rhs,) if up is None else (rhs, up)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, swiglu=up is not None),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tiles,),
            in_specs=[pl.BlockSpec((tm, k),
                                   lambda i, o, gi, ti, l: (ti[i], 0))]
            + [weight] * len(weights),
            out_specs=pl.BlockSpec((tm, n),
                                   lambda i, o, gi, ti, l: (ti[i], 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=NAME,
    )(offsets, group_ids, tile_ids,
      jnp.asarray(layer, jnp.int32).reshape(1), lhs, *weights)
