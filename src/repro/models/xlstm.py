"""xLSTM blocks: mLSTM (matrix memory, parallelizable) and sLSTM
(scalar memory, sequential recurrence).  Follows Beck et al. 2024
(arXiv:2405.04517) with exponential gating and max-stabilizers.

mLSTM trains in the attention-like parallel form (one S^2 pass with the
cumulative-forget decay matrix) and decodes with the exact (dh x dh)
matrix-memory recurrence.  sLSTM is a genuine per-step recurrence
(lax.scan over time) with block-diagonal recurrent weights per head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import dense_init, rmsnorm, rmsnorm_init

Array = jnp.ndarray


# ====================== mLSTM ======================

def mlstm_init(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    d_in = 2 * d
    nh = cfg.lstm_heads
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.dtype)
    return {
        "up": dense_init(ks[0], d, 2 * d_in, dt),      # [x_m, z]
        "wq": dense_init(ks[1], d_in, d_in, dt),
        "wk": dense_init(ks[2], d_in, d_in, dt),
        "wv": dense_init(ks[3], d_in, d_in, dt),
        "wif": dense_init(ks[4], d_in, 2 * nh, dt, scale=0.1),
        "norm": rmsnorm_init(d_in, dt),
        "down": dense_init(ks[5], d_in, d, dt),
    }


def _mlstm_parallel(q, k, v, logi, logf):
    """q,k,v: (B,S,nh,dh); logi/logf: (B,S,nh).  Stabilized parallel form."""
    B, S, nh, dh = q.shape
    cf = jnp.cumsum(logf, axis=1)                        # (B,S,nh)
    # D_ij = cf_i - cf_j + logi_j  for j <= i
    Dm = (cf[:, :, None, :] - cf[:, None, :, :] +
          logi[:, None, :, :])                            # (B,Si,Sj,nh)
    tri = jnp.tril(jnp.ones((S, S), bool))
    Dm = jnp.where(tri[None, :, :, None], Dm, -jnp.inf)
    m = Dm.max(axis=2)                                   # (B,Si,nh)
    dmat = jnp.exp(Dm - m[:, :, None, :])
    qk = jnp.einsum("bihd,bjhd->bijh", q, k) / (dh ** 0.5)
    w = qk * dmat
    denom = jnp.maximum(jnp.abs(w.sum(2)), jnp.exp(-m))  # (B,Si,nh)
    h = jnp.einsum("bijh,bjhd->bihd", w, v) / denom[..., None]
    return h


def _mlstm_chunked(q, k, v, logi, logf, chunk: int):
    """Chunkwise-parallel mLSTM: within-chunk quadratic D-matrix,
    cross-chunk (C, n, m) matrix-memory carry.  O(S*L) memory instead of
    O(S^2) — the §Perf fix for the mLSTM prefill memory wall; exactly
    equal (up to fp) to the full parallel form.
    """
    B, S, nh, dh = q.shape
    L = min(chunk, S)
    nc = -(-S // L)
    pad = nc * L - S
    if pad:
        z4 = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = (jnp.pad(a, z4) for a in (q, k, v))
        logi = jnp.pad(logi, ((0, 0), (0, pad), (0, 0)),
                       constant_values=-1e9)    # pad tokens: no input
        logf = jnp.pad(logf, ((0, 0), (0, pad), (0, 0)))
    def cshape(a):
        return a.reshape(B, nc, L, *a.shape[2:]).swapaxes(0, 1)
    qc, kc, vc, ic, fc = map(cshape, (q, k, v, logi, logf))
    scale = 1.0 / (dh ** 0.5)

    def body(carry, inp):
        C_prev, n_prev, m_prev = carry          # (B,nh,dh,dh),(B,nh,dh),(B,nh)
        qq, kk, vv, li, lf = inp                # (B,L,...)
        b = jnp.cumsum(lf, axis=1)              # (B,L,nh) inclusive
        # intra-chunk D matrix
        Dm = b[:, :, None, :] - b[:, None, :, :] + li[:, None, :, :]
        tri = jnp.tril(jnp.ones((Dm.shape[1], Dm.shape[1]), bool))
        Dm = jnp.where(tri[None, :, :, None], Dm, -jnp.inf)
        m_loc = Dm.max(axis=2)                  # (B,Li,nh)
        a_inter = b + m_prev[:, None, :]        # log-scale of prev state
        m_i = jnp.maximum(m_loc, a_inter)
        w = (jnp.einsum("bihd,bjhd->bijh", qq, kk) * scale *
             jnp.exp(Dm - m_i[:, :, None, :]))
        scale_prev = jnp.exp(a_inter - m_i)     # (B,Li,nh)
        num = (jnp.einsum("bijh,bjhd->bihd", w, vv) +
               jnp.einsum("bihd,bhde,bih->bihe", qq * scale,
                          C_prev, scale_prev))
        den_loc = w.sum(2)
        den_prev = jnp.einsum("bihd,bhd->bih", qq * scale,
                              n_prev) * scale_prev
        den = jnp.maximum(jnp.abs(den_loc + den_prev), jnp.exp(-m_i))
        h = num / den[..., None]

        # carry update
        g = b[:, -1]                            # (B,nh) total log-decay
        m_kv = (g[:, None, :] - b + li).max(axis=1)      # (B,nh)
        m_new = jnp.maximum(g + m_prev, m_kv)
        wj = jnp.exp(g[:, None, :] - b + li - m_new[:, None, :])
        C_new = (jnp.exp(g + m_prev - m_new)[..., None, None] * C_prev +
                 jnp.einsum("bjh,bjhd,bjhe->bhde", wj, kk, vv))
        n_new = (jnp.exp(g + m_prev - m_new)[..., None] * n_prev +
                 jnp.einsum("bjh,bjhd->bhd", wj, kk))
        return (C_new, n_new, m_new), h

    C0 = jnp.zeros((B, nh, dh, dh), jnp.float32)
    n0 = jnp.zeros((B, nh, dh), jnp.float32)
    m0 = jnp.full((B, nh), -1e9, jnp.float32)
    _, h = jax.lax.scan(body, (C0, n0, m0), (qc, kc, vc, ic, fc))
    h = h.swapaxes(0, 1).reshape(B, nc * L, nh, dh)[:, :S]
    return h


def mlstm_apply(params: dict, u: Array, cfg: ModelConfig, *,
                bidirectional: bool = False) -> Array:
    B, S, d = u.shape
    d_in = 2 * d
    nh = cfg.lstm_heads
    dh = d_in // nh

    def one(u):
        xu = u @ params["up"]
        x_m, z = jnp.split(xu, 2, axis=-1)
        q = (x_m @ params["wq"]).reshape(B, S, nh, dh)
        k = (x_m @ params["wk"]).reshape(B, S, nh, dh)
        v = (x_m @ params["wv"]).reshape(B, S, nh, dh)
        gif = (x_m @ params["wif"]).astype(jnp.float32)
        logi, f_raw = jnp.split(gif.reshape(B, S, 2, nh), 2, axis=2)
        logi = logi[:, :, 0]
        logf = -jax.nn.softplus(-f_raw[:, :, 0])          # log sigmoid
        qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
        if cfg.mlstm_chunk and cfg.mlstm_chunk < S:
            h = _mlstm_chunked(qf, kf, vf, logi, logf, cfg.mlstm_chunk)
        else:
            h = _mlstm_parallel(qf, kf, vf, logi, logf)
        h = h.reshape(B, S, d_in).astype(u.dtype)
        h = rmsnorm(params["norm"], h, cfg.norm_eps) * jax.nn.silu(z)
        return h @ params["down"]

    y = one(u)
    if bidirectional:
        y = y + jnp.flip(one(jnp.flip(u, axis=1)), axis=1)
    return y


def mlstm_init_cache(cfg: ModelConfig, batch: int, dtype) -> dict:
    d_in = 2 * cfg.d_model
    nh = cfg.lstm_heads
    dh = d_in // nh
    return {
        "C": jnp.zeros((batch, nh, dh, dh), jnp.float32),
        "n": jnp.zeros((batch, nh, dh), jnp.float32),
        "m": jnp.full((batch, nh), -1e9, jnp.float32),
    }


def mlstm_decode(params: dict, u: Array, cache: dict,
                 cfg: ModelConfig) -> tuple[Array, dict]:
    """u: (B,1,d).  Exact matrix-memory recurrence."""
    B, _, d = u.shape
    d_in = 2 * d
    nh = cfg.lstm_heads
    dh = d_in // nh
    xu = u[:, 0] @ params["up"]
    x_m, z = jnp.split(xu, 2, axis=-1)
    q = (x_m @ params["wq"]).reshape(B, nh, dh).astype(jnp.float32)
    k = (x_m @ params["wk"]).reshape(B, nh, dh).astype(jnp.float32)
    v = (x_m @ params["wv"]).reshape(B, nh, dh).astype(jnp.float32)
    gif = (x_m @ params["wif"]).astype(jnp.float32).reshape(B, 2, nh)
    logi, logf = gif[:, 0], -jax.nn.softplus(-gif[:, 1])
    m_new = jnp.maximum(logf + cache["m"], logi)
    a = jnp.exp(logf + cache["m"] - m_new)[..., None]
    b = jnp.exp(logi - m_new)[..., None]
    C = cache["C"] * a[..., None] + b[..., None] * (
        k[..., :, None] * v[..., None, :])
    n = cache["n"] * a + b * k
    num = jnp.einsum("bhd,bhde->bhe", q / (dh ** 0.5), C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh",
                                         q / (dh ** 0.5), n)),
                      jnp.exp(-m_new))
    h = (num / den[..., None]).reshape(B, d_in).astype(u.dtype)
    h = rmsnorm(params["norm"], h, cfg.norm_eps) * jax.nn.silu(z)
    y = (h @ params["down"])[:, None]
    return y, {"C": C, "n": n, "m": m_new}


# ====================== sLSTM ======================

def slstm_init(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    nh = cfg.lstm_heads
    dh = d // nh
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.dtype)
    return {
        "w": dense_init(ks[0], d, 4 * d, dt),            # i,f,z,o
        "r": (jax.random.truncated_normal(ks[1], -2, 2, (nh, dh, 4 * dh)) *
              (1.0 / dh ** 0.5)).astype(dt),
        "norm": rmsnorm_init(d, dt),
        "down": dense_init(ks[2], d, d, dt),
    }


def _slstm_cell(params, x_t, state, cfg: ModelConfig):
    """x_t: (B, 4*d) pre-activations from inputs; state dict."""
    nh = cfg.lstm_heads
    d = x_t.shape[-1] // 4
    dh = d // nh
    h_prev = state["h"]                                   # (B,nh,dh)
    rec = jnp.einsum("bhd,hde->bhe", h_prev,
                     params["r"].astype(jnp.float32))     # (B,nh,4*dh)
    raw = x_t.reshape(-1, nh, 4 * dh).astype(jnp.float32) + rec
    i_r, f_r, z_r, o_r = jnp.split(raw, 4, axis=-1)
    logi, logf = i_r, -jax.nn.softplus(-f_r)
    m_new = jnp.maximum(logf + state["m"], logi)
    a, b = jnp.exp(logf + state["m"] - m_new), jnp.exp(logi - m_new)
    c = a * state["c"] + b * jnp.tanh(z_r)
    n = a * state["n"] + b
    h = jax.nn.sigmoid(o_r) * c / jnp.maximum(n, 1e-6)
    return {"c": c, "n": n, "m": m_new, "h": h}


def slstm_apply(params: dict, u: Array, cfg: ModelConfig, *,
                bidirectional: bool = False) -> Array:
    B, S, d = u.shape
    nh = cfg.lstm_heads
    dh = d // nh

    def one(u):
        pre = u @ params["w"]                             # (B,S,4d)
        state = {"c": jnp.zeros((B, nh, dh), jnp.float32),
                 "n": jnp.zeros((B, nh, dh), jnp.float32),
                 "m": jnp.full((B, nh, dh), -1e9, jnp.float32),
                 "h": jnp.zeros((B, nh, dh), jnp.float32)}

        def step(state, x_t):
            new = _slstm_cell(params, x_t, state, cfg)
            return new, new["h"]

        _, hs = jax.lax.scan(step, state, pre.transpose(1, 0, 2))
        h = hs.transpose(1, 0, 2, 3).reshape(B, S, d).astype(u.dtype)
        h = rmsnorm(params["norm"], h, cfg.norm_eps)
        return h @ params["down"]

    y = one(u)
    if bidirectional:
        y = y + jnp.flip(one(jnp.flip(u, axis=1)), axis=1)
    return y


def slstm_init_cache(cfg: ModelConfig, batch: int, dtype) -> dict:
    nh = cfg.lstm_heads
    dh = cfg.d_model // nh
    z = jnp.zeros((batch, nh, dh), jnp.float32)
    return {"c": z, "n": z, "m": jnp.full((batch, nh, dh), -1e9,
                                          jnp.float32), "h": z}


def slstm_decode(params: dict, u: Array, cache: dict,
                 cfg: ModelConfig) -> tuple[Array, dict]:
    B, _, d = u.shape
    pre = (u[:, 0] @ params["w"])
    new = _slstm_cell(params, pre, cache, cfg)
    h = new["h"].reshape(B, d).astype(u.dtype)
    h = rmsnorm(params["norm"], h, cfg.norm_eps)
    return (h @ params["down"])[:, None], new
