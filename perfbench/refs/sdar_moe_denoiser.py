"""Plain reference of SDAR's layer (Qwen3-MoE) on one chip's expert share,
as a denoiser, in jax.numpy.

Follows the published decoder layer: pre-norm RMSNorm; q/k/v projections
(``num_attention_heads`` query heads over ``num_key_value_heads`` KV
heads, each ``head_dim`` wide); RMSNorm of each head of q and of k over
``head_dim``; rotary position embedding (rotate-half, theta from the
configuration); softmax attention under SDAR's block-causal mask (query
q sees key k iff ``k // b <= q // b``, b the block length); o and the
residual add; RMSNorm, the router ``x @ W_r`` over all
``num_experts_published`` experts, a float32 softmax, top
``num_experts_per_tok``, the gates renormalised to sum to one, and the
SwiGLU experts, gated and added to the residual; final RMSNorm and an
untied LM head.  No time input: ``t`` is accepted and not read.

The chip's share: only the experts this chip holds (``num_experts`` of
them from ``expert_offset``) contribute.  Plainly: every held expert runs
over every token, and its output is weighted by that token's gate for
it, which is 0 where the token did not route to it; the sum over the
held experts is the layer's partial result, as the program's is.

It imports nothing of the program; it shares the dense reference's
matmul, norm and RoPE helpers (``dense_denoiser.mm``, ``rmsnorm``,
``rope``, and the activation and operand types of each mode) and its
modes (``highest``, ``float32``, ``bfloat16``, ``fp8``).  In ``highest``
mode it runs under ``jax.default_matmul_precision("highest")``.  It reads the weights the
benchmark made, in the layout they are served in: ``embed``, ``head``,
``ln_f/scale``, and per layer (stacked on a leading axis under
``unit/b0``) ``ln1/scale``, ``attn/{wq,wk,wv,wo}``,
``attn/{q_norm,k_norm}/scale``, ``ln2/scale``, ``moe/router`` (d x E)
and ``moe/{gate,up,down}`` (held experts first).  The stack runs one
layer at a time, one compiled program for every layer.
"""
from __future__ import annotations

import contextlib
import math
from functools import partial

import jax
import jax.numpy as jnp

from perfbench.refs.dense_denoiser import (F32, MODES, _act, _operand, mm,
                                           rmsnorm, rope)

__all__ = ["hidden", "logits", "MODES"]


def _precision(mode: str):
    return (jax.default_matmul_precision("highest") if mode == "highest"
            else contextlib.nullcontext())


def _shape(conf: dict) -> tuple:
    return (conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["head_dim"], float(conf["rms_norm_eps"]),
            float(conf["rope_theta"]), int(conf["block_length"]),
            conf["num_experts_per_tok"], conf["expert_offset"],
            conf["num_experts"])


@partial(jax.jit, static_argnames=("mode",))
def _embed(params, tokens, *, mode):
    return params["embed"][tokens].astype(_act(mode))


def _attention(x, p, shape, mode):
    heads, kv, hd, eps, theta, block = shape[:6]
    act, opd = _act(mode), _operand(mode)
    b, n, _ = x.shape
    h = rmsnorm(x, p["ln1"]["scale"], eps, mode)
    a = p["attn"]
    q = mm(h, a["wq"], mode).reshape(b, n, heads, hd)
    k = mm(h, a["wk"], mode).reshape(b, n, kv, hd)
    v = mm(h, a["wv"], mode).reshape(b, n, kv, hd).astype(F32)
    q = rope(rmsnorm(q, a["q_norm"]["scale"], eps, mode), theta)
    k = rope(rmsnorm(k, a["k_norm"]["scale"], eps, mode), theta)
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    q, k = q.astype(act).astype(opd), k.astype(act).astype(opd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=F32) / math.sqrt(hd)
    pos = jnp.arange(n) // block
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(act).astype(opd)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(act).astype(opd),
                   preferred_element_type=F32).astype(act)
    return (x + mm(o.reshape(b, n, heads * hd), a["wo"], mode)).astype(act)


def _experts(x, p, shape, mode):
    eps, top, offset, held = shape[3], shape[6], shape[7], shape[8]
    act = _act(mode)
    h = rmsnorm(x, p["ln2"]["scale"], eps, mode)
    m = p["moe"]
    probs = jax.nn.softmax(mm(h, m["router"], mode).astype(F32), axis=-1)
    g, idx = jax.lax.top_k(probs, top)                      # (b, n, top)
    g = g / g.sum(-1, keepdims=True)
    every = jnp.arange(probs.shape[-1])
    gates = (g[..., None] * (idx[..., None] == every)).sum(-2)  # (b, n, E)
    gates = gates[..., offset:offset + held]                # held experts
    he = h[:, None]                                         # (b, 1, n, d)
    u = jax.nn.silu(mm(he, m["gate"], mode).astype(F32)) * mm(
        he, m["up"], mode).astype(F32)                      # (b, H, n, f)
    y = mm(u.astype(act), m["down"], mode).astype(F32)      # (b, H, n, d)
    y = jnp.einsum("bend,bne->bnd", y, gates,
                   precision=jax.lax.Precision.HIGHEST)
    return (x + y).astype(act)


@partial(jax.jit, static_argnames=("shape", "mode"))
def _layer(x, unit, idx, *, shape, mode):
    p = jax.tree.map(lambda w: w[idx], unit)
    return _experts(_attention(x, p, shape, mode), p, shape, mode)


@partial(jax.jit, static_argnames=("shape", "mode"))
def _final(params, x, *, shape, mode):
    return rmsnorm(x, params["ln_f"]["scale"], shape[3], mode)


def hidden(params, tokens, t, conf: dict, mode: str = "highest"):
    """Final-normed hidden states (B, N, d) of canvases ``tokens`` (B, N);
    ``t`` (B,) is the diffusion time, which this denoiser does not read."""
    del t
    shape = _shape(conf)
    with _precision(mode):
        x = _embed(params, tokens, mode=mode)
        unit = params["unit"]["b0"]
        for i in range(conf["num_hidden_layers"]):
            x = _layer(x, unit, jnp.int32(i), shape=shape, mode=mode)
        return _final(params, x, shape=shape, mode=mode)


def logits(params, h, mode: str = "highest"):
    """LM head over hidden rows (..., d) -> (..., V) float32."""
    with _precision(mode):
        return mm(h, params["head"], mode).astype(F32)
