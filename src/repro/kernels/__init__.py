"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel ships as a package: kernel.py (pl.pallas_call + BlockSpec),
ops.py (jit'd public wrapper) and ref.py (pure-jnp oracle used by the
allclose test sweeps).  Every ops wrapper takes ``interpret=None`` by
default and resolves it with :func:`default_interpret`: compiled Mosaic on
TPU, the Pallas interpreter everywhere else.

The decode layer owns two fused ops, both streaming over vocab tiles
without a (B, N, K) HBM intermediate:

  * ``dndm_update``   — select x0_hat + eq. (9) token update;
  * ``decode_scores`` — (token, score) pairs for the confidence-ranked
    samplers, with an online-logsumexp score head.
"""
import jax


def default_interpret() -> bool:
    """Compiled on TPU, interpret everywhere else."""
    return jax.default_backend() != "tpu"
