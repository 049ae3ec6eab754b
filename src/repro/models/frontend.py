"""Modality frontend *stubs* (the one allowed stub, per the brief).

For [audio] (MusicGen) and [vlm] (Chameleon) we implement the language /
decoder transformer only.  The conv codec (EnCodec) and the vision encoder
(VQ tokenizer) are represented by precomputed embeddings of the correct
shape, produced here (random projections of a seeded key).

``frontend_embeds`` occupy the first ``cfg.frontend_tokens`` positions of
the sequence (early fusion): the model overwrites its token embeddings at
those positions with the provided vectors.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig


def fake_frontend_embeds(key, cfg: ModelConfig, batch: int):
    """Stand-in for EnCodec frames / ViT patch embeddings."""
    if not cfg.frontend:
        return None
    return jax.random.normal(
        key, (batch, cfg.frontend_tokens, cfg.d_model),
        jnp.dtype(cfg.dtype)) * 0.02


def fuse(h, frontend_embeds):
    """Early fusion: overwrite the first F positions."""
    if frontend_embeds is None:
        return h
    F = frontend_embeds.shape[1]
    return jnp.concatenate([frontend_embeds.astype(h.dtype), h[:, F:]],
                           axis=1)
