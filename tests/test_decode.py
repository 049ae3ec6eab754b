"""Decode layer: fused_update backend parity (Pallas interpret vs pure-JAX
reference, plus compiled Pallas on TPU), argmax and Gumbel-sample modes,
aligned and padded shapes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import decode, noise
from repro.core.samplers import SamplerConfig

# compiled Mosaic only exists on TPU; CPU CI compares interpret vs reference
BACKENDS = ["reference", "interpret"] + (
    ["pallas"] if jax.default_backend() == "tpu" else [])

# (2, 16, 128): block-aligned.  (1, 13, 100): N and K both need padding;
# with block_n=8 / block_v=64 the grid is multi-block in both dimensions.
SHAPES = [(2, 16, 128), (1, 13, 100)]


@pytest.mark.parametrize("B,N,K", SHAPES)
@pytest.mark.parametrize("version", [1, 2])
@pytest.mark.parametrize("mode", ["argmax", "sample"])
def test_fused_update_backend_parity(B, N, K, version, mode, key):
    ks = jax.random.split(key, 4)
    logits = jax.random.normal(ks[0], (B, N, K))
    x = jax.random.randint(ks[1], (B, N), 0, K)
    tau = jax.random.randint(ks[2], (B, N), 1, 8)
    nz = noise.absorbing(K)
    cfg = SamplerConfig(x0_mode=mode, temperature=0.7)
    for t in (1, 4, 7):
        outs = [
            np.asarray(decode.fused_update(
                ks[3], logits, x, tau, t, nz, cfg, version=version,
                backend=b, block_n=8, block_v=64))
            for b in BACKENDS
        ]
        for b, o in zip(BACKENDS[1:], outs[1:]):
            assert (o == outs[0]).all(), (b, t)


def test_fused_update_bf16_and_multinomial(key):
    """bf16 logits and a mask-free noise dist go through every backend."""
    B, N, K = 2, 24, 96
    ks = jax.random.split(key, 4)
    logits = jax.random.normal(ks[0], (B, N, K), jnp.bfloat16)
    x = jax.random.randint(ks[1], (B, N), 0, K)
    tau = jax.random.randint(ks[2], (B, N), 1, 6)
    nz = noise.multinomial(K)
    cfg = SamplerConfig(x0_mode="argmax")
    outs = [np.asarray(decode.fused_update(ks[3], logits, x, tau, 3, nz,
                                           cfg, backend=b))
            for b in BACKENDS]
    for o in outs[1:]:
        assert (o == outs[0]).all()


def test_fused_update_matches_decode_tokens(key):
    """With tau == t everywhere, fused_update returns exactly the decoded
    x0_hat — the same tokens decode_tokens picks (shared decode math)."""
    B, N, K = 2, 16, 64
    ks = jax.random.split(key, 2)
    logits = jax.random.normal(ks[0], (B, N, K))
    x = jnp.zeros((B, N), jnp.int32)
    tau = jnp.full((B, N), 5, jnp.int32)
    nz = noise.absorbing(K)
    for mode in ("argmax", "sample"):
        cfg = SamplerConfig(x0_mode=mode)
        for backend in BACKENDS:
            fused = decode.fused_update(ks[1], logits, x, tau, 5, nz, cfg,
                                        backend=backend)
            tok, score = decode.decode_tokens(ks[1], logits, nz, cfg)
            assert (np.asarray(fused) == np.asarray(tok)).all(), (mode,
                                                                  backend)
        assert np.isfinite(np.asarray(score)).all()
        # the absorbing [MASK] id must never be decoded as a clean token
        assert not (np.asarray(tok) == nz.mask_id).any()


@pytest.mark.parametrize("B,N,K", SHAPES)
@pytest.mark.parametrize("mode", ["argmax", "sample"])
@pytest.mark.parametrize("noise_kind", ["absorbing", "multinomial"])
def test_decode_tokens_backend_parity(B, N, K, mode, noise_kind, key):
    """(token, score) parity across backends: tokens bitwise, scores
    allclose (online vs direct logsumexp), padded shapes included."""
    ks = jax.random.split(key, 2)
    logits = jax.random.normal(ks[0], (B, N, K))
    nz = noise.get(noise_kind, K)
    cfg = SamplerConfig(x0_mode=mode, temperature=0.7)
    ref_tok, ref_score = decode.decode_tokens(ks[1], logits, nz, cfg,
                                              backend="reference")
    for b in BACKENDS[1:]:
        tok, score = decode.decode_tokens(ks[1], logits, nz, cfg,
                                          backend=b, block_n=8, block_v=64)
        assert (np.asarray(tok) == np.asarray(ref_tok)).all(), (b, mode)
        np.testing.assert_allclose(np.asarray(score), np.asarray(ref_score),
                                   atol=2e-5, rtol=2e-5)


def test_decode_tokens_agrees_with_fused_update_all_backends(key):
    """The (token) half of decode_tokens is the same selection fused_update
    applies — bitwise, across every backend pairing."""
    B, N, K = 2, 13, 100                      # padded in both dims
    ks = jax.random.split(key, 2)
    logits = jax.random.normal(ks[0], (B, N, K))
    x = jnp.zeros((B, N), jnp.int32)
    tau = jnp.full((B, N), 3, jnp.int32)
    nz = noise.absorbing(K)
    for mode in ("argmax", "sample"):
        cfg = SamplerConfig(x0_mode=mode)
        for bf in BACKENDS:
            fused = decode.fused_update(ks[1], logits, x, tau, 3, nz, cfg,
                                        backend=bf, block_n=8, block_v=64)
            for bd in BACKENDS:
                tok, _ = decode.decode_tokens(ks[1], logits, nz, cfg,
                                              backend=bd, block_n=8,
                                              block_v=64)
                assert (np.asarray(fused) == np.asarray(tok)).all(), (bf, bd)


def test_decode_tokens_env_override(monkeypatch, key):
    """REPRO_DECODE_BACKEND steers decode_tokens exactly like fused_update."""
    B, N, K = 1, 8, 32
    ks = jax.random.split(key, 2)
    logits = jax.random.normal(ks[0], (B, N, K))
    nz = noise.absorbing(K)
    cfg = SamplerConfig(x0_mode="sample")
    ref_tok, ref_score = decode.decode_tokens(ks[1], logits, nz, cfg,
                                              backend="reference")
    monkeypatch.setenv("REPRO_DECODE_BACKEND", "interpret")
    tok, score = decode.decode_tokens(ks[1], logits, nz, cfg)  # auto
    assert (np.asarray(tok) == np.asarray(ref_tok)).all()
    np.testing.assert_allclose(np.asarray(score), np.asarray(ref_score),
                               atol=2e-5, rtol=2e-5)


def test_decode_tokens_scores_are_chosen_logprob(key):
    """Scores == log-softmax of the chosen token (the top-k rank key)."""
    B, N, K = 2, 8, 32
    ks = jax.random.split(key, 2)
    logits = jax.random.normal(ks[0], (B, N, K))
    nz = noise.multinomial(K)
    cfg = SamplerConfig(x0_mode="argmax", temperature=0.5)
    tok, score = decode.decode_tokens(ks[1], logits, nz, cfg)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32) / 0.5, axis=-1)
    want = np.take_along_axis(np.asarray(logp),
                              np.asarray(tok)[..., None], -1)[..., 0]
    np.testing.assert_allclose(np.asarray(score), want, atol=1e-6)


def test_backend_resolution(monkeypatch):
    assert decode.resolve_backend("reference") == "reference"
    assert decode.resolve_backend("auto") in decode.BACKENDS
    monkeypatch.setenv("REPRO_DECODE_BACKEND", "interpret")
    assert decode.default_backend() == "interpret"
    monkeypatch.setenv("REPRO_DECODE_BACKEND", "nope")
    with pytest.raises(ValueError):
        decode.default_backend()
    with pytest.raises(ValueError):
        decode.resolve_backend("nope")


def test_tpu_refuses_non_kernel_backend_override(monkeypatch):
    """On a TPU the serving path never drops to the interpreter or the
    reference behind the caller's back; explicit backend= still works."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_DECODE_BACKEND", raising=False)
    assert decode.resolve_backend() == "pallas"
    monkeypatch.setenv("REPRO_DECODE_BACKEND", "pallas")
    assert decode.resolve_backend() == "pallas"
    for env in ("interpret", "reference"):
        monkeypatch.setenv("REPRO_DECODE_BACKEND", env)
        with pytest.raises(ValueError, match="TPU"):
            decode.resolve_backend()
        assert decode.resolve_backend(env) == env
