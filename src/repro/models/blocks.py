"""Block registry: init / full-sequence apply / cache init / decode step
for every block kind, plus the residual wiring and pre-norms.

Every block has the same external contract so the model can scan or unroll
heterogeneous patterns:

  init(key, cfg)                          -> params
  apply(params, x, cfg, mode)             -> (y, aux)       # full sequence
  init_cache(cfg, batch, max_seq, dtype)  -> cache
  decode(params, x, cache, pos, cfg)      -> (y, new_cache)  # one token
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention, mamba2, moe, xlstm
from repro.models.config import ModelConfig
from repro.models.layers import mlp, mlp_init, rmsnorm, rmsnorm_init

Array = jnp.ndarray


# ---------------- attention-family blocks (attn / swa / moe / shared) ----

def _attn_init(key, cfg: ModelConfig, is_moe: bool) -> dict:
    k1, k2 = jax.random.split(key)
    dt = jnp.dtype(cfg.dtype)
    p = {"ln1": rmsnorm_init(cfg.d_model, dt),
         "attn": attention.init(k1, cfg),
         "ln2": rmsnorm_init(cfg.d_model, dt)}
    if is_moe:
        p["moe"] = moe.init(k2, cfg)
    elif cfg.d_ff > 0:
        p["mlp"] = mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_type, dt)
    return p


def _attn_apply(params, x, cfg: ModelConfig, *, causal: bool, window: int,
                is_moe: bool, experts=None):
    # named scopes: each sub-layer, its pre-norm and residual add
    with jax.named_scope("attention"):
        h = rmsnorm(params["ln1"], x, cfg.norm_eps)
        x = x + attention.apply(params["attn"], h, cfg, causal=causal,
                                window=window)
    aux = {}
    with jax.named_scope("mlp"):
        h = rmsnorm(params["ln2"], x, cfg.norm_eps)
        if is_moe:
            y, aux = moe.apply(params["moe"], h, cfg, experts)
            x = x + y
        elif "mlp" in params:
            x = x + mlp(params["mlp"], h, cfg.mlp_type)
    return x, aux


def _attn_decode(params, x, cache, pos, cfg: ModelConfig, *, window: int,
                 is_moe: bool):
    h = rmsnorm(params["ln1"], x, cfg.norm_eps)
    y, cache = attention.decode_step(params["attn"], h, cache, pos, cfg,
                                     window=window)
    x = x + y
    h = rmsnorm(params["ln2"], x, cfg.norm_eps)
    if is_moe:
        y, _ = moe.apply(params["moe"], h, cfg)
        x = x + y
    elif "mlp" in params:
        x = x + mlp(params["mlp"], h, cfg.mlp_type)
    return x, cache


# ---------------- dispatch ----------------

def init(kind: str, key, cfg: ModelConfig) -> dict:
    if kind in ("attn", "swa", "shared_attn"):
        return _attn_init(key, cfg, is_moe=False)
    if kind == "moe":
        return _attn_init(key, cfg, is_moe=True)
    dt = jnp.dtype(cfg.dtype)
    k1, k2 = jax.random.split(key)
    ln = rmsnorm_init(cfg.d_model, dt)
    if kind == "mamba2":
        return {"ln": ln, "mixer": mamba2.init(k1, cfg)}
    if kind == "mlstm":
        return {"ln": ln, "mixer": xlstm.mlstm_init(k1, cfg)}
    if kind == "slstm":
        return {"ln": ln, "mixer": xlstm.slstm_init(k1, cfg)}
    raise KeyError(kind)


def apply(kind: str, params: dict, x: Array, cfg: ModelConfig, *,
          causal: bool, experts=None) -> tuple[Array, dict]:
    """One block.  ``experts``: a "moe" block's expert weights stacked
    over the layer scan and the layer's index (``moe.apply``)."""
    bidir = not causal
    # "swa" blocks always window; "moe" blocks window when configured
    # (Mixtral: SWA + MoE in the same layer)
    window = cfg.sliding_window if kind in ("swa", "moe") else 0
    if kind in ("attn", "swa", "shared_attn", "moe"):
        return _attn_apply(params, x, cfg, causal=causal, window=window,
                           is_moe=(kind == "moe"), experts=experts)
    h = rmsnorm(params["ln"], x, cfg.norm_eps)
    if kind == "mamba2":
        y = mamba2.apply(params["mixer"], h, cfg, bidirectional=bidir)
    elif kind == "mlstm":
        y = xlstm.mlstm_apply(params["mixer"], h, cfg, bidirectional=bidir)
    elif kind == "slstm":
        y = xlstm.slstm_apply(params["mixer"], h, cfg, bidirectional=bidir)
    else:
        raise KeyError(kind)
    return x + y, {}


def init_cache(kind: str, cfg: ModelConfig, batch: int, max_seq: int,
               dtype) -> dict:
    if kind in ("attn", "shared_attn"):
        return attention.init_cache(cfg, batch, max_seq, 0, dtype)
    if kind in ("swa", "moe"):
        return attention.init_cache(cfg, batch, max_seq,
                                    cfg.sliding_window, dtype)
    if kind == "mamba2":
        return mamba2.init_cache(cfg, batch, dtype)
    if kind == "mlstm":
        return xlstm.mlstm_init_cache(cfg, batch, dtype)
    if kind == "slstm":
        return xlstm.slstm_init_cache(cfg, batch, dtype)
    raise KeyError(kind)


def decode(kind: str, params: dict, x: Array, cache: dict, pos: Array,
           cfg: ModelConfig) -> tuple[Array, dict]:
    if kind in ("attn", "shared_attn"):
        return _attn_decode(params, x, cache, pos, cfg, window=0,
                            is_moe=False)
    if kind in ("swa", "moe"):
        return _attn_decode(params, x, cache, pos, cfg,
                            window=cfg.sliding_window,
                            is_moe=(kind == "moe"))
    h = rmsnorm(params["ln"], x, cfg.norm_eps)
    if kind == "mamba2":
        y, cache = mamba2.decode_step(params["mixer"], h, cache, cfg)
    elif kind == "mlstm":
        y, cache = xlstm.mlstm_decode(params["mixer"], h, cache, cfg)
    elif kind == "slstm":
        y, cache = xlstm.slstm_decode(params["mixer"], h, cache, cfg)
    else:
        raise KeyError(kind)
    return x + y, cache
