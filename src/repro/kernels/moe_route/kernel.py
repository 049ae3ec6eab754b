"""Row movement of the expert layer's routing — Pallas kernels.

A layer that holds a share of the experts routes only the token-expert
pairs whose expert it holds; sorted by expert, they are the first
``count`` rows of a pair buffer of T*k rows (enough for any routing).
These two kernels move those rows and no others.

A row cannot be copied on its own from or into HBM, where arrays are
tiled by 8 (16 in bfloat16) rows, nor loaded at a traced row offset from
a packed bfloat16 array in VMEM.  So each kernel keeps the side it
addresses row by row in VMEM as float32, and moves the pair buffer's
rows in whole tiles of ``tm`` rows: the grid runs over the tiles that
hold held pairs, ``ceil(count / tm)`` of them, a traced number.

``moe_dispatch`` writes the buffer's tiles: row r of a tile is token
``order[r] // k``, read from the tokens held in VMEM.  Tiles past the
held pairs are never visited, so those rows are left unwritten.

``moe_combine`` reads the buffer's tiles: each held row, times its
gate, is added in float32 to its token's row of a (T, d) accumulator in
VMEM, which is cast once after the last tile.  Rows past ``count``, in
the last tile, are added to a spare accumulator row that is never cast
(they may hold anything, NaN included); a token sums its
held pairs in the order of their rows (by expert), and a token none of
whose pairs is held gets zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DISPATCH = "moe_dispatch"
COMBINE = "moe_combine"
# the VMEM the two kernels may take: SDAR's tokens (2048 x 2048) as
# float32 (16 MiB) beside a bfloat16 copy (8 MiB, twice for the output)
VMEM_LIMIT = 64 * 2**20
CHUNK = 256                         # rows a whole-array cast takes at once
UNROLL = 8                          # rows a loop step copies
SPILL = 8                           # accumulator rows past the tokens'


def vmem_bytes(t: int, d: int, itemsize: int) -> int:
    """VMEM the larger of the two kernels holds for (t, d) tokens: the
    float32 side addressed by row and the tokens, or the result twice."""
    return t * d * (4 + 2 * itemsize)


def _cast_rows(src, dst):
    """dst[...] = src[...] cast, :data:`CHUNK` rows at a time."""
    rows = src.shape[0]
    step = CHUNK if rows % CHUNK == 0 else rows

    def body(c, carry):
        r = pl.multiple_of(c * step, step)
        dst[pl.ds(r, step), :] = src[pl.ds(r, step), :].astype(dst.dtype)
        return carry
    jax.lax.fori_loop(0, rows // step, body, 0)


def _each_row(tm: int, body):
    """``body(i)`` for each row i < ``tm`` of a tile, :data:`UNROLL` rows
    to a loop step."""
    def step(g, carry):
        for u in range(UNROLL):
            body(g * UNROLL + u)
        return carry
    jax.lax.fori_loop(0, tm // UNROLL, step, 0)


def _dispatch_kernel(order_ref, x_ref, out_ref, x32, tile, *, k: int,
                     tm: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        _cast_rows(x_ref, x32)

    def row(i):
        s = order_ref[j * tm + i] // k
        tile[pl.ds(i, 1), :] = x32[pl.ds(s, 1), :]
    _each_row(tm, row)
    out_ref[...] = tile[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "tm", "interpret"))
def moe_dispatch(x, order, count, *, k: int, tm: int = 128,
                 interpret=False):
    """x: (T, d); order: (T*k,) int32, the pairs sorted held first, T*k a
    multiple of ``tm``; count: the number of held pairs.  Returns
    (T*k, d) in ``x.dtype``: row r < ``count`` is ``x[order[r] // k]``;
    the rows of tiles past ``count`` are undefined."""
    (t, d), m = x.shape, order.shape[0]
    if m % tm:
        raise ValueError(f"pairs {m} must divide the row tile {tm}")
    count = jnp.asarray(count, jnp.int32)
    return pl.pallas_call(
        functools.partial(_dispatch_kernel, k=k, tm=tm),
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(count, tm),),
            in_specs=[pl.BlockSpec((t, d), lambda j, *_: (0, 0),
                                   pipeline_mode=pl.Buffered(1))],
            out_specs=pl.BlockSpec((tm, d), lambda j, *_: (j, 0)),
            scratch_shapes=[pltpu.VMEM((t, d), jnp.float32),
                            pltpu.VMEM((tm, d), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=DISPATCH,
    )(order.astype(jnp.int32), x)


def _combine_kernel(tok_ref, gate_ref, out_ref, y_ref, acc, tile, *,
                    tm: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros(acc.shape, acc.dtype)

    tile[...] = out_ref[...].astype(jnp.float32)

    def row(i):
        r = j * tm + i
        acc[pl.ds(tok_ref[r], 1), :] += tile[pl.ds(i, 1), :] * gate_ref[r]
    _each_row(tm, row)

    @pl.when(j == pl.num_programs(0) - 1)
    def _():
        _cast_rows(acc.at[:y_ref.shape[0]], y_ref)


@functools.partial(jax.jit, static_argnames=("t", "dtype", "tm",
                                             "interpret"))
def moe_combine(out, tok, gate, count, *, t: int, dtype, tm: int = 128,
                interpret=False):
    """out: (M, d), the pair buffer, M a multiple of ``tm``; tok: (M,)
    int32 and gate: (M,) float32, each row's token and gate, with ``t``
    as the token of every row past the held ones; count: the number of
    held rows, the first ones.  Returns (t, d) in ``dtype``: per token
    the float32 sum of gate times row over its held rows."""
    m, d = out.shape
    if m % tm:
        raise ValueError(f"rows {m} must divide the row tile {tm}")
    count = jnp.asarray(count, jnp.int32)
    return pl.pallas_call(
        functools.partial(_combine_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((t, d), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(jnp.maximum(pl.cdiv(count, tm), 1),),
            in_specs=[pl.BlockSpec((tm, d), lambda j, *_: (j, 0))],
            out_specs=pl.BlockSpec((t, d), lambda j, *_: (0, 0)),
            scratch_shapes=[pltpu.VMEM((t + SPILL, d), jnp.float32),
                            pltpu.VMEM((tm, d), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=COMBINE,
    )(tok.astype(jnp.int32), gate.astype(jnp.float32), out)
