"""jit'd wrapper for the streaming (token, score) decode.

Mirrors ``dndm_update.ops``: pads N and K up to block multiples
(``dndm_update.ops.block_sizes``) instead of raising on non-divisible
shapes, and auto-detects the execution backend — compiled
Mosaic on TPU, the Pallas interpreter elsewhere (``interpret=None``, the
default).  Pass ``interpret`` explicitly to force either mode.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.decode_scores.kernel import decode_scores_kernel
from repro.kernels.dndm_update.ops import (_round_up, block_sizes,
                                           record_padding)


@partial(jax.jit, static_argnames=("temperature", "block_n", "block_v",
                                   "interpret"))
def decode_scores(logits, *, mask=None, gumbel=None,
                  temperature: float = 1.0, block_n: int = 256,
                  block_v: int = 1024, interpret: bool | None = None):
    """logits: (B,N,K); optional ``mask`` (K,) f32 additive logit penalty
    and ``gumbel`` (B,N,K) f32 noise (sample mode).  Returns
    (tokens (B,N) int32, scores (B,N) f32)."""
    if interpret is None:
        interpret = default_interpret()
    B, N, K = logits.shape
    bn, bkv = block_sizes(N, K, block_n, block_v, interpret)
    pad_n = _round_up(N, bn) - N
    pad_k = _round_up(K, bkv) - K
    record_padding("decode_scores", N, K, pad_n, pad_k)
    if mask is None:
        mask = jnp.zeros((K,), jnp.float32)
    mask = mask.astype(jnp.float32).reshape(1, K)
    if pad_n:
        logits = jnp.pad(logits, ((0, 0), (0, pad_n), (0, 0)))
        if gumbel is not None:
            gumbel = jnp.pad(gumbel, ((0, 0), (0, pad_n), (0, 0)))
    if pad_k:
        # -inf keeps padded vocab lanes out of the running max AND out of
        # the online logsumexp (exp(-inf) == 0); gumbel and mask pad with
        # 0 so the padded lanes stay at exactly -inf.
        logits = jnp.pad(logits, ((0, 0), (0, 0), (0, pad_k)),
                         constant_values=-jnp.inf)
        mask = jnp.pad(mask, ((0, 0), (0, pad_k)))
        if gumbel is not None:
            gumbel = jnp.pad(gumbel, ((0, 0), (0, 0), (0, pad_k)))
    tok, score = decode_scores_kernel(logits, mask, gumbel=gumbel,
                                      temperature=temperature, block_n=bn,
                                      block_v=bkv, interpret=interpret)
    return tok[:, 0, :N], score[:, 0, :N]
