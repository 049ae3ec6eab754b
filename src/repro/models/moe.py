"""Mixture-of-Experts MLP: one dropless layer.

Top-k routing (Mixtral: 8e top-2; Llama-4-Maverick: 128e top-1; SDAR /
Qwen3-MoE: 128e top-8).  The router is a float32 softmax over all
``n_experts``; the k gates are renormalised to sum to one.

An expert layer may hold only a share of the experts (``held_experts``
of them, from ``expert_offset``): one chip's part of an expert-parallel
deployment.  The router keeps its full width, and the layer returns the
part of the result its own experts give, the chip's partial sum; what
the absent experts add is left out.

The expert layer (``_dispatch_ffn``, which serving and training
use) drops nothing: the token-expert pairs routed to a held expert are
sorted by expert and run through grouped matmuls (``kernels/moe_gmm``:
the SwiGLU's gate and up in one, then down) whose work follows the
pairs, so a token's output never depends on the other tokens of its
batch.  Given the layer's place in the stacked weights of a layer scan
(``experts``), the kernel reads the weights there rather than from a
per-layer copy.  The buffer has T*K rows, enough for any routing, but
only its first ``sum(sizes)`` rows, the held pairs', are read.  A layer
that holds a share of the experts (``n_held < n_experts``) fills and
reads only those rows, on a TPU, in ``kernels/moe_route``'s
``moe_dispatch`` and ``moe_combine``; a layer that holds every expert,
where every row is live, keeps XLA's gathers over the whole buffer.
Named scopes ``moe_route`` (router, top-k, sort, dispatch, combine) and
``moe_experts`` (the grouped matmuls) split it inside the block's
``mlp`` scope.

Auxiliary losses: router z-loss and load-balance loss (returned, weighted
by the trainer).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import moe_route as route
from repro.kernels.moe_gmm import gmm
from repro.models.config import ModelConfig
from repro.models.layers import dense_init

Array = jnp.ndarray


def init(key, cfg: ModelConfig) -> dict:
    """The router over all ``n_experts`` and the held experts' weights."""
    E, d, ff = cfg.n_held, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    std = 1.0 / d ** 0.5
    p = {
        "router": dense_init(ks[0], d, cfg.n_experts, dt, scale=0.1),
        "down": (jax.random.truncated_normal(ks[3], -2, 2, (E, ff, d)) *
                 (1.0 / ff ** 0.5)).astype(dt),
    }
    if cfg.mlp_type == "swiglu":
        p["gate"] = (jax.random.truncated_normal(ks[1], -2, 2, (E, d, ff)) *
                     std).astype(dt)
    p["up"] = (jax.random.truncated_normal(ks[2], -2, 2, (E, d, ff)) *
               std).astype(dt)
    return p


def apply(params: dict, x: Array, cfg: ModelConfig,
          experts: tuple | None = None) -> tuple[Array, dict]:
    """x: (B, S, d) -> (y, aux_losses).

    ``experts``, optional, is ``(stack, layer)``: this block's expert
    weights stacked over the layer scan, of which ``params`` holds slice
    ``layer``; the grouped-matmul kernel reads them in the stack.
    """
    B, S, d = x.shape
    T = B * S
    y, aux = _dispatch_ffn(params, x.reshape(T, d), cfg, experts)
    return y.reshape(B, S, d), aux


def _router(params: dict, xt: Array, cfg: ModelConfig):
    """(T, d) -> router logits and probabilities (T, E) in float32, and
    the top-k gates, renormalised, with their expert ids (T, K)."""
    logits = jnp.dot(xt, params["router"],
                     preferred_element_type=jnp.float32)     # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.experts_per_token)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    return logits, probs, gate_vals, expert_idx


def _aux(logits, probs, flat_e, cfg: ModelConfig) -> dict:
    E = cfg.n_experts
    fe = jnp.bincount(flat_e, length=E) / flat_e.shape[0]
    return {"load_balance": (E * jnp.sum(probs.mean(0) * fe)
                             ).astype(jnp.float32),
            "router_z": jnp.mean(
                jax.nn.logsumexp(logits, -1) ** 2).astype(jnp.float32)}


def _dispatch_ffn(params: dict, xt: Array, cfg: ModelConfig,
                  experts: tuple | None = None) -> tuple[Array, dict]:
    """Dropless route + held experts + combine on (T, d) tokens: the
    pairs routed to a held expert, sorted by expert, run through grouped
    matmuls over a buffer of T*K rows (enough for any routing); each
    token then sums its held pairs' outputs times their gates in float32.
    Buffer rows past the held pairs are never read.

    Which rows move: a layer that holds every expert fills and reads the
    whole buffer with XLA's gathers.  A layer that holds a share
    (``n_held < n_experts``) moves only the held pairs' rows, on a TPU,
    with the ``kernels/moe_route`` kernels: the dispatch writes the first
    ``sum(sizes)`` rows and the combine reads them back.  The kernels hold
    the tokens in VMEM as float32, so they take at most 48 MiB / (8 d)
    tokens in bfloat16 (3,072 at d 2048, 12 rows of 256 positions); a
    larger batch falls back to XLA's gathers.  ``held_share`` in the
    returned aux is the share of the T*K pairs that are held."""
    T, d = xt.shape
    K, H = cfg.experts_per_token, cfg.n_held
    share = cfg.n_held < cfg.n_experts
    moved = share and route.use_kernel(xt)
    with jax.named_scope("moe_route"):
        logits, probs, gate_vals, expert_idx = _router(params, xt, cfg)
        flat_e = expert_idx.reshape(-1)                      # (T*K,)
        local = flat_e - cfg.expert_offset
        held = (local >= 0) & (local < H)
        local = jnp.where(held, local, H)
        order = jnp.argsort(local, stable=True)              # held first
        sizes = (local[:, None] == jnp.arange(H)).sum(0, dtype=jnp.int32)
        if moved:
            count = sizes.sum()                              # held pairs
            h = route.dispatch(xt, order, count, K)
        else:
            h = route.dispatch_ref(xt, order, K)             # (T*K, d)

    def stacked(first, second=None):
        if experts is None:
            return None
        stack, layer = experts
        return (stack[first], stack[second] if second else None,
                layer)
    with jax.named_scope("moe_experts"):
        if cfg.mlp_type == "swiglu":
            a = gmm(h, params["gate"], sizes, params["up"],
                    stacked=stacked("gate", "up"))
        else:
            a = jax.nn.gelu(gmm(h, params["up"], sizes,
                                stacked=stacked("up")))
        out = gmm(a, params["down"], sizes, stacked=stacked("down"))
    with jax.named_scope("moe_route"):
        if moved:
            y = route.combine(out, gate_vals, order, held, count,
                              xt.dtype)
        else:
            y = route.combine_ref(out, route.slots(order), held,
                                  gate_vals, xt.dtype)
    aux = _aux(logits, probs, flat_e, cfg)
    aux["held_share"] = (held.mean(dtype=jnp.float32) if share
                         else jnp.float32(1.0))
    return y, aux
