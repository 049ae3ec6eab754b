"""Decode-update backend layer.

Every sampler's hot path decodes x0_hat from the (B, N, K) denoiser
logits and folds it into the running token buffer.  This module is the
single place where that happens — ``fused_update`` (select x0 + eq. (9))
and ``decode_tokens`` ((token, score) pairs for the confidence-ranked
samplers) — behind three interchangeable backends:

  * ``"pallas"``    — the streaming kernels in ``kernels/dndm_update``
                      and ``kernels/decode_scores`` compiled to Mosaic;
                      never materialize the log-softmax / argmax
                      intermediate in HBM.
  * ``"interpret"`` — the same kernel under the Pallas interpreter
                      (CPU/GPU debugging; slow, bit-identical tokens).
  * ``"reference"`` — pure jnp (fast on CPU, the correctness oracle).

``backend="auto"`` (the default everywhere) resolves to ``"pallas"`` on
TPU and ``"reference"`` elsewhere.  Off the TPU, ``REPRO_DECODE_BACKEND``
forces a backend process-wide (``interpret`` drives the kernels on CPU).
On the TPU it may only say ``pallas``: the serving path never falls back
to the interpreter or the reference there behind the caller's back.  An
explicit ``backend=`` argument is always honoured.

Both ops run under ``jax.named_scope("decode")``, as does the stepwise
path's per-row noise draw, so a device trace charges the noise, the
padding and the kernel to the decode path.

Decode modes follow ``SamplerConfig.x0_mode``: ``"argmax"`` picks the
highest adjusted logit; ``"sample"`` draws categorically via the
Gumbel-max trick (argmax of logits/temp + mask + Gumbel(0,1) noise), so
all three backends produce bitwise-identical tokens under a fixed key.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.decode_scores import ops as _sops
from repro.kernels.decode_scores import ref as _sref
from repro.kernels.dndm_update import ops as _ops
from repro.kernels.dndm_update import ref as _ref

Array = jnp.ndarray

BACKENDS = ("pallas", "interpret", "reference")


def default_backend() -> str:
    env = os.environ.get("REPRO_DECODE_BACKEND", "").strip()
    on_tpu = jax.default_backend() == "tpu"
    backend = env or ("pallas" if on_tpu else "reference")
    if backend not in BACKENDS:
        raise ValueError(f"REPRO_DECODE_BACKEND={env!r}; pick one of "
                         f"{BACKENDS}")
    if on_tpu and backend != "pallas":
        raise ValueError(f"REPRO_DECODE_BACKEND={env!r} on a TPU: the decode "
                         "kernels are compiled there; unset it, or pass "
                         "backend= explicitly")
    return backend


def resolve_backend(backend: str | None = "auto") -> str:
    if backend in (None, "auto"):
        return default_backend()
    if backend not in BACKENDS:
        raise ValueError(f"unknown decode backend {backend!r}; pick one of "
                         f"{BACKENDS} or 'auto'")
    return backend


def _gumbel(key: jax.Array, shape, x0_mode: str) -> Array | None:
    if x0_mode == "argmax":
        return None
    if x0_mode != "sample":
        raise ValueError(f"unknown x0_mode {x0_mode!r}")
    return jax.random.gumbel(key, shape, jnp.float32)


def fused_update(key: jax.Array, logits: Array, x: Array, tau: Array, t,
                 noise, cfg, *, version: int = 1, backend: str = "auto",
                 block_n: int = 256, block_v: int = 1024,
                 gumbel: Array | None = None) -> Array:
    """Decode x0_hat and apply the eq. (9) token update in one pass.

    ``x_{t-1} = where(tau == t, x0_hat, x_t)`` (``tau >= t`` for
    Algorithm 3 / version=2).  Returns the updated tokens (B, N) int32.
    All backends agree bitwise on the result for a fixed ``key``.

    ``gumbel`` overrides the internally drawn Gumbel tensor (sample mode
    only) — the stepwise serving path draws one (N, K) slab per row from
    that row's own key stream so that rows at different diffusion times
    reproduce their solo-run noise bit-for-bit; ``key`` may then be None.

    Memory note: argmax mode is the fully streaming path.  Sample mode
    materializes a (B, N, K) f32 Gumbel tensor so that every backend sees
    identical noise (the bitwise-parity contract); replacing it with
    in-kernel per-tile counter-based PRNG would recover the streaming
    property at the cost of backend-portable determinism.
    """
    backend = resolve_backend(backend)
    if obs.enabled():
        # counted at trace time when called from jitted code: one inc per
        # compiled program, i.e. "which backend serves this sampler"
        obs.counter("decode.backend_calls").inc(op="fused_update",
                                                backend=backend)
    with jax.named_scope("decode"):
        mask = noise.logit_mask(jnp.float32)
        if gumbel is None:
            gumbel = _gumbel(key, logits.shape, cfg.x0_mode)
        t = jnp.asarray(t, jnp.int32)
        if backend == "reference":
            out = _ref.dndm_update_ref(logits, x, tau.astype(jnp.int32),
                                       t.reshape(1), version=version,
                                       mask=mask,
                                       temperature=cfg.temperature,
                                       gumbel=gumbel)
            return out.astype(jnp.int32)
        return _ops.dndm_update(logits, x, tau, t, mask=mask, gumbel=gumbel,
                                version=version,
                                temperature=cfg.temperature,
                                block_n=block_n, block_v=block_v,
                                interpret=(backend == "interpret"))


def decode_tokens(key: jax.Array, logits: Array, noise, cfg, *,
                  backend: str = "auto", block_n: int = 256,
                  block_v: int = 1024,
                  gumbel: Array | None = None) -> tuple[Array, Array]:
    """Pick x0_hat from logits; returns (tokens (B,N), scores (B,N)).

    Scores are the per-token log-probabilities of the chosen token —
    exactly the quantity RDM-k / DNDM-k rank on (paper App. E).  Tokens
    come from the same adjusted-logit argmax / Gumbel-max the fused
    kernel computes, so they agree with ``fused_update`` bitwise across
    every backend.  Backend resolution is identical to ``fused_update``
    (``backend="auto"``, ``REPRO_DECODE_BACKEND`` respected); the
    pallas/interpret path is the streaming ``kernels/decode_scores`` op —
    a running (max, argmax, logsumexp) triple in VMEM across vocab tiles,
    never materializing the (B, N, K) log-softmax in HBM.

    ``gumbel`` overrides the internal draw exactly as in
    :func:`fused_update` (per-row noise for the stepwise serving path).
    """
    backend = resolve_backend(backend)
    if obs.enabled():
        obs.counter("decode.backend_calls").inc(op="decode_tokens",
                                                backend=backend)
    with jax.named_scope("decode"):
        mask = noise.logit_mask(jnp.float32)
        if gumbel is None:
            gumbel = _gumbel(key, logits.shape, cfg.x0_mode)
        if backend == "reference":
            return _sref.decode_scores_ref(logits, mask=mask,
                                           temperature=cfg.temperature,
                                           gumbel=gumbel)
        return _sops.decode_scores(logits, mask=mask, gumbel=gumbel,
                                   temperature=cfg.temperature,
                                   block_n=block_n, block_v=block_v,
                                   interpret=(backend == "interpret"))
