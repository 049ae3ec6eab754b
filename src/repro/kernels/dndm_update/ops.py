"""jit'd wrapper for the fused DNDM decode-update.

Pads N and K up to block multiples (see ``block_sizes``) instead of
raising on non-divisible shapes, and auto-detects
the execution backend: compiled Mosaic on TPU, the Pallas interpreter
elsewhere (``interpret=None``, the default).  Pass ``interpret`` explicitly
to force either mode.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import default_interpret
from repro.kernels.dndm_update.kernel import dndm_update_kernel


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def record_padding(kernel: str, N: int, K: int, pad_n: int,
                   pad_k: int) -> None:
    """Padding-overhead gauges for a kernel call.  Shapes are static, so
    when the op is jitted this runs at trace time: one record per
    compiled program, describing the waste baked into it."""
    if not obs.enabled():
        return
    total = (N + pad_n) * (K + pad_k)
    obs.counter("kernel.traces").inc(kernel=kernel)
    obs.gauge("kernel.pad_n").set(pad_n, kernel=kernel)
    obs.gauge("kernel.pad_k").set(pad_k, kernel=kernel)
    obs.gauge("kernel.pad_fraction",
              "fraction of padded (N+pad)(K+pad) elements that is waste"
              ).set(round(1.0 - (N * K) / total, 6), kernel=kernel)


def block_sizes(N: int, K: int, block_n: int, block_v: int,
                interpret: bool) -> tuple[int, int]:
    """(token block, vocab block) for a streaming decode kernel call.

    The token block is the lane dimension of the (B, 1, N) per-token
    operands and the sublane dimension of the (B, N, K) logit blocks; the
    vocab block is the logits' lane dimension.  Compiled Mosaic needs a
    lane block that is a multiple of 128 or the whole padded axis, so the
    caller's sizes are rounded up to that outside interpret mode.  The
    interpreter takes them as given (small multi-block grids in tests).
    """
    n8, k128 = _round_up(N, 8), _round_up(K, 128)
    if interpret:
        return min(block_n, n8), min(block_v, k128)
    bn = _round_up(block_n, 128)
    return (n8 if n8 <= bn else bn), min(_round_up(block_v, 128), k128)


@partial(jax.jit, static_argnames=("version", "block_n", "block_v",
                                   "temperature", "interpret"))
def dndm_update(logits, x, tau, t, *, mask=None, gumbel=None,
                version: int = 1, block_n: int = 256, block_v: int = 1024,
                temperature: float = 1.0, interpret: bool | None = None):
    """logits: (B,N,K); x, tau: (B,N) int32; t scalar int32.

    Optional ``mask`` (K,) f32 additive logit penalty and ``gumbel``
    (B,N,K) f32 noise (sample mode).  Returns updated tokens (B,N) int32.
    """
    if interpret is None:
        interpret = default_interpret()
    B, N, K = logits.shape
    bn, bkv = block_sizes(N, K, block_n, block_v, interpret)
    pad_n = _round_up(N, bn) - N
    pad_k = _round_up(K, bkv) - K
    record_padding("dndm_update", N, K, pad_n, pad_k)
    if mask is None:
        mask = jnp.zeros((K,), jnp.float32)
    mask = mask.astype(jnp.float32).reshape(1, K)
    if pad_n:
        logits = jnp.pad(logits, ((0, 0), (0, pad_n), (0, 0)))
        x = jnp.pad(x, ((0, 0), (0, pad_n)))
        tau = jnp.pad(tau, ((0, 0), (0, pad_n)))
        if gumbel is not None:
            gumbel = jnp.pad(gumbel, ((0, 0), (0, pad_n), (0, 0)))
    if pad_k:
        # -inf keeps padded vocab lanes out of the running max; gumbel and
        # mask pad with 0 so the padded lanes stay at exactly -inf.
        logits = jnp.pad(logits, ((0, 0), (0, 0), (0, pad_k)),
                         constant_values=-jnp.inf)
        mask = jnp.pad(mask, ((0, 0), (0, pad_k)))
        if gumbel is not None:
            gumbel = jnp.pad(gumbel, ((0, 0), (0, 0), (0, pad_k)))
    t_arr = jnp.asarray(t, jnp.int32).reshape(1)
    out = dndm_update_kernel(logits, mask, x.astype(jnp.int32)[:, None],
                             tau.astype(jnp.int32)[:, None], t_arr,
                             gumbel=gumbel, version=version,
                             temperature=temperature, block_n=bn,
                             block_v=bkv, interpret=interpret)
    return out[:, 0, :N]
