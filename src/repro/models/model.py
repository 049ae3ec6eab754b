"""Top-level Model: embeddings, superblock stack (one layer scan),
diffusion time conditioning, frontend fusion, LM head, KV/state caches.

The forward pass names its parts with ``jax.named_scope``:
``time_embed``, ``attention`` and ``mlp`` (``models/blocks.py``) and
``lm_head`` (final norm and unembedding).  The names reach the compiled
program's op metadata, so a device trace can charge each operation to
its part of the denoiser.

The block pattern is decomposed into ``unit * n_super`` (config enforces
periodicity).  Non-shared block weights are stacked along a leading
``n_super`` axis and the stack runs as one ``lax.scan``.
``shared_attn`` blocks hold a single weight set used by every occurrence
(Zamba-style), while each occurrence gets its own cache slot.  A ``moe``
block is also handed its expert weights' whole stack and the layer's
index, so its grouped-matmul kernel reads them in place (``moe.apply``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import blocks, frontend
from repro.models.config import ModelConfig
from repro.models.layers import dense_init, rmsnorm, rmsnorm_init, time_embed, time_embed_init

Array = jnp.ndarray


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.unit, self.n_super = cfg.superblock()

    # ---------------- init ----------------

    def init(self, key) -> dict:
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        keys = jax.random.split(key, 4 + len(self.unit))
        params: dict = {
            "embed": dense_init(keys[0], cfg.vocab_size, cfg.d_model, dt,
                                scale=cfg.vocab_size ** 0.5 * 0.02),
            "ln_f": rmsnorm_init(cfg.d_model, dt),
        }
        if not cfg.tie_embeddings:
            params["head"] = dense_init(keys[1], cfg.d_model,
                                        cfg.vocab_size, dt)
        if cfg.time_conditioning:
            params["time"] = time_embed_init(keys[2], cfg.d_model, dt)
        if "shared_attn" in self.unit:
            params["shared"] = blocks.init("shared_attn", keys[3], cfg)

        unit_params = {}
        for i, kind in enumerate(self.unit):
            if kind == "shared_attn":
                continue
            ks = jax.random.split(keys[4 + i], self.n_super)
            stacked = [blocks.init(kind, ks[j], cfg)
                       for j in range(self.n_super)]
            unit_params[f"b{i}"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *stacked)
        params["unit"] = unit_params
        return params

    # ---------------- full-sequence forward ----------------

    def forward(self, params: dict, tokens: Array, t: Array | None = None,
                frontend_embeds: Array | None = None,
                causal: bool | None = None) -> tuple[Array, dict]:
        """tokens: (B, S) -> (logits (B, S, V), aux losses)."""
        cfg = self.cfg
        if causal is None:
            causal = not cfg.bidirectional
        h = params["embed"][tokens]
        if t is not None and cfg.time_conditioning:
            with jax.named_scope("time_embed"):
                h = h + time_embed(params["time"], t, cfg.d_model)[:, None]
        h = frontend.fuse(h, frontend_embeds)

        def superblock(h, unit_slice, layer=None):
            aux_tot = jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)
            lb, rz = aux_tot
            for i, kind in enumerate(self.unit):
                p = (params["shared"] if kind == "shared_attn"
                     else unit_slice[f"b{i}"])
                experts = None
                if kind == "moe":        # the gradient goes to the slice
                    experts = (jax.lax.stop_gradient(
                        params["unit"][f"b{i}"]["moe"]), layer)
                h, aux = blocks.apply(kind, p, h, cfg, causal=causal,
                                      experts=experts)
                if aux:
                    lb = lb + aux["load_balance"]
                    rz = rz + aux["router_z"]
            return h, (lb, rz)

        body = superblock
        if cfg.remat:
            body = jax.checkpoint(superblock)

        xs = (params["unit"],)
        if "moe" in self.unit:           # and each layer's index
            xs += (jnp.arange(self.n_super, dtype=jnp.int32),)
        h, (lbs, rzs) = jax.lax.scan(lambda h, xs: body(h, *xs), h, xs)
        lb, rz = lbs.sum(), rzs.sum()

        with jax.named_scope("lm_head"):
            h = rmsnorm(params["ln_f"], h, cfg.norm_eps)
            logits = h @ (params["embed"].T if cfg.tie_embeddings
                          else params["head"])
        return logits, {"load_balance": lb, "router_z": rz}

    # ---------------- diffusion denoiser adapter ----------------

    def denoise_fn(self, params: dict, cond: dict | None = None):
        """Wrap into the samplers' ``denoise_fn(x_t, t, cond)`` contract.

        ``cond`` may hold {"prefix_tokens": (B, P)} for conditional
        generation (source prefix stays clean; logits returned for the
        target segment only) and {"frontend_embeds": ...}.
        """
        def fn(x_t, t, cond_rt):
            c = cond_rt if cond_rt is not None else (cond or {})
            fe = c.get("frontend_embeds")
            prefix = c.get("prefix_tokens")
            if prefix is not None:
                full = jnp.concatenate([prefix, x_t], axis=1)
                logits, _ = self.forward(params, full, t, fe, causal=False)
                return logits[:, prefix.shape[1]:]
            logits, _ = self.forward(params, x_t, t, fe, causal=False)
            return logits
        return fn

    # ---------------- decode (serving) ----------------

    def init_cache(self, batch: int, max_seq: int, dtype=None) -> dict:
        cfg = self.cfg
        dt = jnp.dtype(dtype or cfg.dtype)
        cache = {}
        for i, kind in enumerate(self.unit):
            per = [blocks.init_cache(kind, cfg, batch, max_seq, dt)
                   for _ in range(self.n_super)]
            cache[f"b{i}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
        return cache

    def decode_step(self, params: dict, token: Array, cache: dict,
                    pos: Array) -> tuple[Array, dict]:
        """token: (B, 1) int32; pos: scalar int32.  Returns (logits (B,1,V),
        new cache).  Runs the stack causally with per-layer caches."""
        cfg = self.cfg
        h = params["embed"][token]

        def superblock(h, slices):
            unit_slice, cache_slice = slices
            new_cache = {}
            for i, kind in enumerate(self.unit):
                p = (params["shared"] if kind == "shared_attn"
                     else unit_slice.get(f"b{i}"))
                h, new_cache[f"b{i}"] = blocks.decode(
                    kind, p, h, cache_slice[f"b{i}"], pos, cfg)
            return h, new_cache

        # shared params are closed over; scan consumes (params, cache)
        h, new_cache = jax.lax.scan(superblock, h, (params["unit"], cache))

        h = rmsnorm(params["ln_f"], h, cfg.norm_eps)
        logits = h @ (params["embed"].T if cfg.tie_embeddings
                      else params["head"])
        return logits, new_cache

    # ---------------- bookkeeping ----------------

    def param_count(self, params) -> int:
        return sum(int(p.size) for p in jax.tree.leaves(params))

    def active_param_count(self, params) -> int:
        """MoE-aware: router + active experts only (for 6*N_active*D)."""
        cfg = self.cfg
        total = self.param_count(params)
        if not cfg.n_experts:
            return total
        moe_leaves = 0
        for i, kind in enumerate(self.unit):
            if kind != "moe":
                continue
            sub = params["unit"][f"b{i}"]["moe"]
            for name in ("gate", "up", "down"):
                if name in sub:
                    moe_leaves += int(sub[name].size)
        inactive = moe_leaves * (1 - cfg.experts_per_token / cfg.n_experts)
        return int(total - inactive)
