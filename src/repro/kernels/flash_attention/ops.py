"""jit'd public wrapper for the flash-attention kernel.

Accepts the model's (B, S, H, hd) layout, reorders to the kernel's
(B, H, S, hd), and pads sequence lengths up to block multiples (padded
keys are masked with NEG bias; padded queries are sliced off).
``interpret=None`` compiles on TPU and interprets elsewhere.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.flash_attention.kernel import NEG, flash_attention_kernel


@partial(jax.jit, static_argnames=("block_q", "block_k", "interpret"))
def flash_attention(q, k, v, bias, *, block_q: int = 256,
                    block_k: int = 256, interpret: bool | None = None):
    """q,k,v: (B,S,H,hd) (kv heads already repeated); bias: (B,Sq,Sk).
    Returns (B,Sq,H,hd)."""
    if interpret is None:
        interpret = default_interpret()
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        bias = jnp.pad(bias, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        bias = jnp.pad(bias, ((0, 0), (0, 0), (0, pad_k)),
                       constant_values=NEG)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = flash_attention_kernel(qt, kt, vt, bias, block_q=bq, block_k=bk,
                               interpret=interpret)
    o = o.transpose(0, 2, 1, 3)
    return o[:, :Sq]
