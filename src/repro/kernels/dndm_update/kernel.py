"""Fused DNDM decode-update — Pallas kernel.

The inner loop of Algorithm 1/3 is: decode x0_hat from the logits and
apply eq. (9): x_{t-1} = where(tau == t, x0_hat, x_t) (``tau >= t`` for
Algorithm 3).  Done naively this materializes the (B, N, K) softmax /
argmax intermediate in HBM; fused, it is one streaming pass: logit tiles
are consumed block-by-block over the vocab with a running (max, argmax)
pair in VMEM, and the token update happens in-register on the last vocab
block.

Two decode modes share the same streaming loop:

  * argmax — x0_hat = argmax_K(logits / temp + mask);
  * sample — Gumbel-max: x0_hat = argmax_K(logits / temp + mask + g)
    with g ~ Gumbel(0, 1) supplied as a tile-streamed input, so every
    backend (compiled, interpret, pure-JAX reference) sees identical
    noise and the decoded tokens match bitwise under a fixed key.

The additive ``mask`` row (shape (1, K)) carries the noise distribution's
forbidden-token penalty (e.g. never decode [MASK] as a clean token).

grid = (B, num_token_blocks, num_vocab_blocks), vocab innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dndm_kernel(*refs, nk: int, bkv: int, version: int,
                 temperature: float, has_gumbel: bool):
    if has_gumbel:
        (logits_ref, gumbel_ref, mask_ref, x_ref, tau_ref, t_ref, o_ref,
         m_scr, idx_scr) = refs
    else:
        (logits_ref, mask_ref, x_ref, tau_ref, t_ref, o_ref,
         m_scr, idx_scr) = refs
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        idx_scr[...] = jnp.zeros_like(idx_scr)

    # NOTE: op order (cast, /temp, +mask, +gumbel) must stay in lockstep
    # with ref.adjust_logits — bitwise token parity depends on it.
    a = logits_ref[0].astype(jnp.float32)               # (bn, bkv)
    if temperature != 1.0:
        a = a / temperature
    a = a + mask_ref[0]                                 # (bkv,) broadcast
    if has_gumbel:
        a = a + gumbel_ref[0]
    local_max = a.max(axis=1)
    local_arg = a.argmax(axis=1).astype(jnp.int32) + ik * bkv
    better = local_max > m_scr[...]
    m_scr[...] = jnp.where(better, local_max, m_scr[...])
    idx_scr[...] = jnp.where(better, local_arg, idx_scr[...])

    @pl.when(ik == nk - 1)
    def _flush():
        x = x_ref[0]                                    # (1, bn)
        tau = tau_ref[0]
        t = t_ref[0]
        cond = (tau == t) if version == 1 else (tau >= t)
        o_ref[0] = jnp.where(cond, idx_scr[...], x)


def dndm_update_kernel(logits, mask, x, tau, t, gumbel=None, *,
                       version: int = 1, temperature: float = 1.0,
                       block_n: int = 256, block_v: int = 1024,
                       interpret: bool = True):
    """logits: (B,N,K); mask: (1,K) f32; x, tau: (B,1,N) int32; t: (1,)
    int32; gumbel: optional (B,N,K) f32.  Returns updated tokens (B,1,N)
    int32.

    The per-token operands carry a unit middle axis so that their blocks
    are ``(1, 1, bn)``: Mosaic tiles the last two block dimensions by
    (8, 128) unless they span the whole array axis, and a ``(1, bn)``
    block over a (B, N) array breaks that rule for every B > 1."""
    B, N, K = logits.shape
    bn = min(block_n, N)
    bkv = min(block_v, K)
    if N % bn or K % bkv:
        raise ValueError(f"(N,K)=({N},{K}) must divide blocks ({bn},{bkv}); "
                         "use ops.dndm_update, which pads")
    nn, nk = N // bn, K // bkv

    logit_spec = pl.BlockSpec((1, bn, bkv), lambda b, i, k: (b, i, k))
    in_specs = [logit_spec]
    args = [logits]
    if gumbel is not None:
        in_specs.append(logit_spec)
        args.append(gumbel)
    in_specs += [
        pl.BlockSpec((1, bkv), lambda b, i, k: (0, k)),
        pl.BlockSpec((1, 1, bn), lambda b, i, k: (b, 0, i)),
        pl.BlockSpec((1, 1, bn), lambda b, i, k: (b, 0, i)),
        pl.BlockSpec((1,), lambda b, i, k: (0,)),
    ]
    args += [mask, x, tau, t]

    return pl.pallas_call(
        functools.partial(_dndm_kernel, nk=nk, bkv=bkv, version=version,
                          temperature=temperature,
                          has_gumbel=gumbel is not None),
        grid=(B, nn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bn), lambda b, i, k: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((B, 1, N), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((bn,), jnp.float32),
            pltpu.VMEM((bn,), jnp.int32),
        ],
        interpret=interpret,
    )(*args)
