"""Streaming (token, score) decode — Pallas kernel.

The confidence-ranked samplers (DNDM-K, RDM-k, Mask-Predict, DDIM,
DNDM-C) need more than the argmax token: they rank positions by the
log-probability of the decoded token.  Done naively that materializes the
full (B, N, K) log-softmax in HBM and gathers out of it.  Fused, it is
the same streaming pass as ``dndm_update``: logit tiles are consumed
block-by-block over the vocab with a flash-attention-style online
logsumexp, and both outputs fall out on the last vocab tile:

  * token — running (max, argmax) over the *selection* activation
    ``sel = logits/temp + mask (+ gumbel)``, identical op order to
    ``dndm_update`` / ``ref.adjust_logits`` so tokens stay bitwise equal
    across every backend;
  * score — ``a[token] - logsumexp(a)`` where ``a`` is the adjusted
    logit *without* the Gumbel noise (the rank key is the model's
    log-probability of the chosen token, not the perturbed value).
    ``logsumexp(a)`` is accumulated online as a running (m, sum) pair in
    VMEM; ``a[token]`` is tracked alongside the running argmax.

Nothing of shape (B, N, K) is ever written back to HBM.

grid = (B, num_token_blocks, num_vocab_blocks), vocab innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _decode_scores_kernel(*refs, nk: int, bkv: int, temperature: float,
                          has_gumbel: bool):
    if has_gumbel:
        (logits_ref, gumbel_ref, mask_ref, tok_ref, score_ref,
         sel_m, sel_idx, a_tok, lse_m, lse_s) = refs
    else:
        (logits_ref, mask_ref, tok_ref, score_ref,
         sel_m, sel_idx, a_tok, lse_m, lse_s) = refs
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        sel_m[...] = jnp.full_like(sel_m, -jnp.inf)
        sel_idx[...] = jnp.zeros_like(sel_idx)
        a_tok[...] = jnp.full_like(a_tok, -jnp.inf)
        lse_m[...] = jnp.full_like(lse_m, -jnp.inf)
        lse_s[...] = jnp.zeros_like(lse_s)

    # NOTE: op order (cast, /temp, +mask, +gumbel) must stay in lockstep
    # with ref.adjust_logits — bitwise token parity depends on it.
    a = logits_ref[0].astype(jnp.float32)               # (bn, bkv)
    if temperature != 1.0:
        a = a / temperature
    a = a + mask_ref[0]                                 # (bkv,) broadcast
    sel = a + gumbel_ref[0] if has_gumbel else a

    local_max = sel.max(axis=1)
    local_arg = sel.argmax(axis=1).astype(jnp.int32)
    # adjusted (noise-free) logit at this tile's winner, via one-hot max —
    # no gather along lanes on TPU
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    a_local = jnp.where(lane == local_arg[:, None], a, -jnp.inf).max(axis=1)
    better = local_max > sel_m[...]
    sel_m[...] = jnp.where(better, local_max, sel_m[...])
    sel_idx[...] = jnp.where(better, local_arg + ik * bkv, sel_idx[...])
    a_tok[...] = jnp.where(better, a_local, a_tok[...])

    # online logsumexp over a (padded vocab lanes sit at -inf => exp == 0)
    m_new = jnp.maximum(lse_m[...], a.max(axis=1))
    lse_s[...] = (lse_s[...] * jnp.exp(lse_m[...] - m_new)
                  + jnp.exp(a - m_new[:, None]).sum(axis=1))
    lse_m[...] = m_new

    @pl.when(ik == nk - 1)
    def _flush():
        tok_ref[0] = sel_idx[...][None]
        score_ref[0] = (a_tok[...] - (lse_m[...] + jnp.log(lse_s[...])))[None]


def decode_scores_kernel(logits, mask, gumbel=None, *,
                         temperature: float = 1.0, block_n: int = 256,
                         block_v: int = 1024, interpret: bool = True):
    """logits: (B,N,K); mask: (1,K) f32; gumbel: optional (B,N,K) f32.
    Returns (tokens (B,1,N) int32, scores (B,1,N) f32): the unit middle
    axis keeps the output blocks ``(1, 1, bn)`` legal for Mosaic's (8, 128)
    tiling (see ``dndm_update.kernel``)."""
    B, N, K = logits.shape
    bn = min(block_n, N)
    bkv = min(block_v, K)
    if N % bn or K % bkv:
        raise ValueError(f"(N,K)=({N},{K}) must divide blocks ({bn},{bkv}); "
                         "use ops.decode_scores, which pads")
    nn, nk = N // bn, K // bkv

    logit_spec = pl.BlockSpec((1, bn, bkv), lambda b, i, k: (b, i, k))
    in_specs = [logit_spec]
    args = [logits]
    if gumbel is not None:
        in_specs.append(logit_spec)
        args.append(gumbel)
    in_specs.append(pl.BlockSpec((1, bkv), lambda b, i, k: (0, k)))
    args.append(mask)

    out_spec = pl.BlockSpec((1, 1, bn), lambda b, i, k: (b, 0, i))
    return pl.pallas_call(
        functools.partial(_decode_scores_kernel, nk=nk, bkv=bkv,
                          temperature=temperature,
                          has_gumbel=gumbel is not None),
        grid=(B, nn, nk),
        in_specs=in_specs,
        out_specs=(out_spec, out_spec),
        out_shape=(jax.ShapeDtypeStruct((B, 1, N), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1, N), jnp.float32)),
        scratch_shapes=[
            pltpu.VMEM((bn,), jnp.float32),     # running selection max
            pltpu.VMEM((bn,), jnp.int32),       # running argmax
            pltpu.VMEM((bn,), jnp.float32),     # adjusted logit at argmax
            pltpu.VMEM((bn,), jnp.float32),     # logsumexp running max
            pltpu.VMEM((bn,), jnp.float32),     # logsumexp running sum
        ],
        interpret=interpret,
    )(*args)
