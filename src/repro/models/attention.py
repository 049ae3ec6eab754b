"""Grouped-query attention with RoPE, sliding windows and KV caches.

Three interchangeable inner implementations (``cfg.attn_impl``):
  einsum  — naive S^2 attention (baseline for the roofline memory term)
  blocked — online-softmax over KV chunks in pure JAX (lax.scan); the
            memory-bounded TPU-shaped algorithm and the oracle for the
            Pallas flash kernel
  pallas  — kernels/flash_attention (compiled on TPU, interpreted elsewhere)

Mask semantics: ``causal`` plus optional ``sliding_window`` (only the last
W positions visible).  The diffusion denoiser runs with causal=False.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import apply_rope, dense_init, rope_freqs

Array = jnp.ndarray
NEG = -1e9


def init(key, cfg: ModelConfig) -> dict:
    hd = cfg.hd
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    return {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, dt),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, dt),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, dt),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, dt,
                         scale=1.0 / max(cfg.n_layers, 1) ** 0.5),
    }


def _qkv(params, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    hd = cfg.hd
    q = (x @ params["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = (x @ params["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = (x @ params["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    cos, sin = rope_freqs(hd, cfg.rope_theta, positions)
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def _repeat_kv(k: Array, n_heads: int) -> Array:
    """(B,S,KV,hd) -> (B,S,H,hd) by repeating each kv head H/KV times."""
    B, S, KV, hd = k.shape
    rep = n_heads // KV
    return jnp.repeat(k, rep, axis=2) if rep > 1 else k


def _mask_bias(q_pos: Array, k_pos: Array, causal: bool,
               window: int) -> Array:
    """(..., Sq, Sk) additive bias from position grids."""
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    ok = jnp.ones(diff.shape, bool)
    if causal:
        ok &= diff >= 0
    if window > 0:
        ok &= jnp.abs(diff) < window if not causal else diff < window
    return jnp.where(ok, 0.0, NEG)


def _einsum_attn(q, k, v, bias):
    hd = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (hd ** 0.5)
    logits = logits + bias[:, None] if bias.ndim == 3 else logits + bias
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _blocked_attn(q, k, v, bias, block_k: int, unroll: bool = False):
    """Online-softmax over KV chunks; O(S * block_k) live memory.

    ``unroll=True`` runs the chunk loop as straight-line code instead of
    ``lax.scan`` — used by the dry-run so XLA cost analysis counts every
    chunk (scan bodies are costed once).
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    bk = min(block_k, Sk)
    n_blocks = -(-Sk // bk)
    pad = n_blocks * bk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        bias = jnp.pad(bias, ((0, 0), (0, 0), (0, pad)),
                       constant_values=NEG)
    kb = k.reshape(B, n_blocks, bk, H, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_blocks, bk, H, hd).transpose(1, 0, 2, 3, 4)
    biasb = bias.reshape(B, Sq, n_blocks, bk).transpose(2, 0, 1, 3)

    def body(carry, inp):
        m, l, acc = carry                       # (B,H,Sq), (B,H,Sq), (B,Sq,H,hd)
        kc, vc, bc = inp
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kc) / (hd ** 0.5)
        s = s.astype(jnp.float32) + bc[:, None]
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(-1)
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(q.dtype), vc).astype(jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, Sq, H, hd), jnp.float32)
    if unroll:
        carry = (m0, l0, a0)
        for i in range(n_blocks):
            carry, _ = body(carry, (kb[i], vb[i], biasb[i]))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, biasb))
    l = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (acc / l).astype(q.dtype)


def _inner(q, k, v, bias, cfg: ModelConfig):
    k = _repeat_kv(k, cfg.n_heads)
    v = _repeat_kv(v, cfg.n_heads)
    if cfg.attn_impl == "blocked":
        return _blocked_attn(q, k, v, bias, cfg.attn_block_k)
    if cfg.attn_impl == "blocked_unrolled":
        return _blocked_attn(q, k, v, bias, cfg.attn_block_k, unroll=True)
    if cfg.attn_impl == "pallas":
        from repro.kernels.flash_attention import ops as flash_ops
        return flash_ops.flash_attention(
            q, k, v, bias, block_q=cfg.attn_block_q,
            block_k=cfg.attn_block_k)
    return _einsum_attn(q, k, v, bias)


def apply(params: dict, x: Array, cfg: ModelConfig, *, causal: bool,
          window: int = 0) -> Array:
    """Full-sequence attention.  x: (B, S, d)."""
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = _qkv(params, x, cfg, positions)
    bias = _mask_bias(jnp.arange(S), jnp.arange(S), causal, window)
    bias = jnp.broadcast_to(bias, (B, S, S))
    y = _inner(q, k, v, bias, cfg)
    return y.reshape(B, S, cfg.n_heads * cfg.hd) @ params["wo"]


# ---------------- KV cache decode ----------------

def init_cache(cfg: ModelConfig, batch: int, max_seq: int, window: int,
               dtype) -> dict:
    """Physical cache length: the window for SWA blocks, else max_seq."""
    L = min(max_seq, window) if window else max_seq
    hd = cfg.hd
    return {
        "k": jnp.zeros((batch, L, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, L, cfg.n_kv_heads, hd), dtype),
    }


def decode_step(params: dict, x: Array, cache: dict, pos: Array,
                cfg: ModelConfig, window: int = 0) -> tuple[Array, dict]:
    """One-token decode.  x: (B, 1, d); pos: scalar int32 (current index).

    The cache is a ring buffer of physical length L; slot = pos mod L.
    """
    B = x.shape[0]
    hd = cfg.hd
    L = cache["k"].shape[1]
    positions = jnp.broadcast_to(pos[None], (B, 1))
    q, k_new, v_new = _qkv(params, x, cfg, positions)
    slot = jnp.mod(pos, L)
    k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, slot, 0, 0))
    # absolute position held by each physical slot: the latest write sits
    # at `slot` with position `pos`; slot i holds pos - ((slot - i) mod L)
    idx = jnp.arange(L)
    k_pos = pos - jnp.mod(slot - idx, L)
    valid = k_pos >= 0
    bias = _mask_bias(pos[None], k_pos, causal=True, window=window)
    bias = jnp.where(valid[None, :], bias, NEG)
    bias = jnp.broadcast_to(bias, (B, 1, L))
    y = _inner(q, k, v, bias, cfg)
    y = y.reshape(B, 1, cfg.n_heads * hd) @ params["wo"]
    return y, {"k": k, "v": v}
