"""Plain reference of the dense Transformer denoiser, in jax.numpy.

Follows the published decoder layer (Phi-3 / Llama family): pre-norm
RMSNorm, rotary position embedding on queries and keys (rotate-half form,
theta from the configuration), softmax attention over every position of
the canvas (a denoiser attends both ways; a sliding window applies when
the configuration has one), SwiGLU MLP, final RMSNorm and an untied LM
head.  The denoiser adds one thing to the published model: the diffusion
time ``t`` in [0, 1] enters as sinusoidal features (``d/2`` frequencies
``exp(-i log(1e4) / (d/2 - 1))``, argument ``1000 t``) through a two-layer
SiLU MLP, added to every position's embedding.

It imports nothing of the program.  It reads the weights the benchmark
made, in the layout they are served in: ``embed``, ``head``,
``ln_f/scale``, ``time/{w1,w2}``, and per layer (stacked on a leading
axis under ``unit/b0``) ``ln1/scale``, ``attn/{wq,wk,wv,wo}``,
``ln2/scale``, ``mlp/{gate,up,down}``.

``mode`` picks the arithmetic:

* ``"highest"``: float32 everywhere, matmuls at ``HIGHEST`` precision;
* ``"float32"``: float32 everywhere except the matmul operands, which are
  rounded to bfloat16 and multiplied with float32 accumulation: the
  one-pass matmul a TPU makes of a float32 matmul at default precision;
* ``"bfloat16"``: weights, activations and matmul results in bfloat16
  (norms and softmax in float32, rounded back), the control for a float32
  configuration;
* ``"fp8"``: activations in bfloat16 and every matmul operand rounded to
  float8 e4m3 (per-tensor scale for weights, per-row for activations),
  the control for a bfloat16 configuration.

The stack runs one layer at a time, one compiled program for every
layer, so a model whose float32 weights would not fit runs all the same.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
BF16 = jnp.bfloat16
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
MODES = ("highest", "float32", "bfloat16", "fp8")


def _act(mode: str):
    return F32 if mode in ("highest", "float32") else BF16


def _operand(mode: str):
    """The type matmul operands are rounded to."""
    return F32 if mode == "highest" else BF16


def _fp8(x, axis):
    """Round to float8 e4m3 under an absmax scale along ``axis``."""
    x = x.astype(F32)
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(FP8).astype(F32) * s


def mm(a, w, mode: str):
    if mode == "highest":
        return jnp.matmul(a.astype(F32), w.astype(F32),
                          precision=jax.lax.Precision.HIGHEST)
    if mode == "float32":
        return jnp.matmul(a.astype(BF16), w.astype(BF16),
                          preferred_element_type=F32)
    if mode == "bfloat16":
        return jnp.matmul(a.astype(BF16), w.astype(BF16))
    if mode == "fp8":
        out = jnp.matmul(_fp8(a, -1), _fp8(w, None),
                         precision=jax.lax.Precision.HIGHEST)
        return out.astype(BF16)
    raise ValueError(f"unknown mode {mode!r}; pick one of {MODES}")


def rmsnorm(x, scale, eps, mode):
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(F32)).astype(_act(mode))


def rope(x, theta):
    """x: (B, N, H, hd); rotate-half RoPE at positions 0..N-1."""
    n, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(n, dtype=F32)[:, None] * inv          # (N, hd/2)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def time_features(t, d):
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=F32)
                    * (math.log(10_000.0) / max(half - 1, 1)))
    ang = t.astype(F32)[:, None] * freqs[None, :] * 1000.0
    feats = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)
    return jnp.pad(feats, ((0, 0), (0, d - feats.shape[-1])))


def _shape(conf: dict) -> tuple:
    return (conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["hidden_size"] // conf["num_attention_heads"],
            float(conf["rms_norm_eps"]), float(conf["rope_theta"]),
            int(conf.get("sliding_window") or 0))


@partial(jax.jit, static_argnames=("shape", "mode"))
def _embed(params, tokens, t, *, shape, mode):
    act = _act(mode)
    d = params["embed"].shape[1]
    h = params["embed"][tokens].astype(act)
    tp = params["time"]
    te = mm(jax.nn.silu(mm(time_features(t, d), tp["w1"], mode)
                        .astype(F32)).astype(act), tp["w2"], mode)
    return (h + te.astype(act)[:, None, :]).astype(act)


@partial(jax.jit, static_argnames=("shape", "mode"))
def _layer(x, unit, idx, *, shape, mode):
    heads, kv, hd, eps, theta, window = shape
    act = _act(mode)
    p = jax.tree.map(lambda w: w[idx], unit)
    b, n, d = x.shape
    h = rmsnorm(x, p["ln1"]["scale"], eps, mode)
    a = p["attn"]
    q = rope(mm(h, a["wq"], mode).reshape(b, n, heads, hd), theta)
    k = rope(mm(h, a["wk"], mode).reshape(b, n, kv, hd), theta)
    v = mm(h, a["wv"], mode).reshape(b, n, kv, hd).astype(F32)
    if kv != heads:
        k = jnp.repeat(k, heads // kv, axis=2)
        v = jnp.repeat(v, heads // kv, axis=2)
    opd = _operand(mode)
    q, k = q.astype(act).astype(opd), k.astype(act).astype(opd)
    prec = jax.lax.Precision.HIGHEST if mode == "highest" else None
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec,
                   preferred_element_type=F32) / math.sqrt(hd)
    if window:
        pos = jnp.arange(n)
        s = jnp.where(jnp.abs(pos[:, None] - pos[None, :]) < window, s,
                      -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(act).astype(opd)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(act).astype(opd),
                   precision=prec, preferred_element_type=F32).astype(act)
    x = (x + mm(o.reshape(b, n, heads * hd), a["wo"], mode)).astype(act)
    h = rmsnorm(x, p["ln2"]["scale"], eps, mode)
    m = p["mlp"]
    g = jax.nn.silu(mm(h, m["gate"], mode).astype(F32))
    u = mm(h, m["up"], mode).astype(F32)
    x = x + mm((g * u).astype(act), m["down"], mode)
    return x.astype(act)


@partial(jax.jit, static_argnames=("shape", "mode"))
def _final(params, x, *, shape, mode):
    return rmsnorm(x, params["ln_f"]["scale"], shape[3], mode)


def hidden(params, tokens, t, conf: dict, mode: str = "highest"):
    """Final-normed hidden states (B, N, d) of canvases ``tokens`` (B, N)
    at diffusion times ``t`` (B,) in [0, 1]."""
    shape = _shape(conf)
    x = _embed(params, tokens, t, shape=shape, mode=mode)
    unit = params["unit"]["b0"]
    for i in range(conf["num_hidden_layers"]):
        x = _layer(x, unit, jnp.int32(i), shape=shape, mode=mode)
    return _final(params, x, shape=shape, mode=mode)


def logits(params, h, mode: str = "highest"):
    """LM head over hidden rows (..., d) -> (..., V) float32."""
    return mm(h, params["head"], mode).astype(F32)
