"""Deliverable (f): per-assigned-architecture smoke tests.

For each of the 10 assigned architectures: instantiate the REDUCED
same-family variant (<=2-3 layers, d_model<=512, <=4 experts) and run one
forward + one diffusion train step on CPU, asserting output shapes and
no NaNs.  Decode-capable archs also run a 4-token decode streak.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.core import noise, schedules
from repro.models import Model
from repro.models.frontend import fake_frontend_embeds
from repro.training import AdamW, constant, init_state, make_train_step

ARCHS = list(C.ASSIGNED_ARCHS)


@pytest.fixture(scope="module")
def schnz():
    return schedules.linear(20)


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch, schnz, key):
    cfg = C.get(arch).reduced(bidirectional=True)
    model = Model(cfg)
    params = model.init(key)
    B, S = 2, 32
    tok = jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0,
                             cfg.vocab_size - 1)
    fe = fake_frontend_embeds(jax.random.fold_in(key, 2), cfg, B)
    t = jnp.full((B,), 0.4)

    logits, aux = model.forward(params, tok, t, fe)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all(), arch

    nz = noise.absorbing(cfg.vocab_size)
    opt = AdamW(schedule=constant(1e-3))
    step = jax.jit(make_train_step(model, schnz, nz, opt))
    state = init_state(model, opt, jax.random.fold_in(key, 3))
    batch = {"x0": tok}
    if fe is not None:
        batch["frontend_embeds"] = fe
    state2, metrics = step(state, batch, jax.random.fold_in(key, 4))
    assert np.isfinite(float(metrics["loss"])), arch
    assert int(state2["step"]) == 1
    # params actually moved
    moved = any(
        float(jnp.abs(a - b).max()) > 0
        for a, b in zip(jax.tree.leaves(state["params"]),
                        jax.tree.leaves(state2["params"])))
    assert moved, arch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_step(arch, key):
    cfg = C.get(arch).reduced()          # causal serving mode
    model = Model(cfg)
    params = model.init(key)
    B = 2
    cache = model.init_cache(B, 16)
    tok = jax.random.randint(jax.random.fold_in(key, 5), (B, 4), 0,
                             cfg.vocab_size - 1)
    for i in range(4):
        logits, cache = model.decode_step(params, tok[:, i:i + 1], cache,
                                          jnp.asarray(i))
        assert logits.shape == (B, 1, cfg.vocab_size)
        assert np.isfinite(np.asarray(logits)).all(), arch


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact assigned hyperparameters."""
    spec = {
        "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
        "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
        "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
        "starcoder2-3b": (30, 3072, 24, 2, 12288, 49152),
        "phi3-mini-3.8b": (32, 3072, 32, 32, 8192, 32064),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "deepseek-7b": (30, 4096, 32, 32, 11008, 102400),
        "chameleon-34b": (48, 8192, 64, 8, 22016, 65536),
        "tinyllama-1.1b": (22, 2048, 32, 4, 5632, 32000),
    }[arch]
    cfg = C.get(arch)
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == spec, (arch, got, spec)
    # extras
    if arch == "zamba2-2.7b":
        assert cfg.ssm_state == 64 and "shared_attn" in cfg.block_pattern
    if arch == "mixtral-8x7b":
        assert (cfg.n_experts, cfg.experts_per_token) == (8, 2)
        assert cfg.sliding_window == 4096
    if arch == "llama4-maverick-400b-a17b":
        assert (cfg.n_experts, cfg.experts_per_token) == (128, 1)
    if arch == "xlstm-350m":
        assert {"mlstm", "slstm"} <= set(cfg.block_pattern)
    if arch in ("musicgen-large", "chameleon-34b"):
        assert cfg.frontend is not None and cfg.frontend_tokens > 0

