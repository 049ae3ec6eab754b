"""jit'd wrapper: model layout (B,S,H,P) -> kernel layout, padding to
chunk multiples, slicing the result back.  ``interpret=None`` compiles on
TPU and interprets elsewhere."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.ssd_scan.kernel import ssd_scan_kernel


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dtv, A, Bm, Cm, *, chunk: int = 128,
             interpret: bool | None = None):
    """x: (B,S,H,P); dtv: (B,S,H); A: (H,); Bm/Cm: (B,S,N).
    Returns (y (B,S,H,P), None) matching the chunked-ref signature."""
    if interpret is None:
        interpret = default_interpret()
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    L = min(chunk, S)
    pad = (-S) % L
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dtv = jnp.pad(dtv, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    C = (S + pad) // L
    xk = x.reshape(B, C, L, H, P).transpose(0, 3, 1, 2, 4)
    dtk = dtv.reshape(B, C, L, H).transpose(0, 3, 1, 2)
    Bk = Bm.reshape(B, C, L, N)
    Ck = Cm.reshape(B, C, L, N)
    y = ssd_scan_kernel(xk, dtk, A.astype(jnp.float32), Bk, Ck,
                        interpret=interpret)
    y = y.transpose(0, 2, 3, 1, 4).reshape(B, C * L, H, P)[:, :S]
    return y, None
