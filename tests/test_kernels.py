"""Pallas kernel sweeps: shapes x dtypes vs pure-jnp oracles
(interpret=True on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_scores import ops as ds_ops, ref as ds_ref
from repro.kernels.dndm_update import ops as dndm_ops, ref as dndm_ref
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.ssd_scan import ops as ssd_ops, ref as ssd_ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("B,S,H,hd", [(1, 32, 2, 16), (2, 64, 4, 32),
                                      (1, 128, 2, 64), (2, 48, 3, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, S, H, hd, dtype, causal, key):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, H, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, H, hd), dtype)
    if causal:
        bias = jnp.where(jnp.tril(jnp.ones((S, S), bool)), 0.0, -1e9)
        bias = jnp.broadcast_to(bias, (B, S, S))
    else:
        bias = jnp.zeros((B, S, S))
    out = fa_ops.flash_attention(q, k, v, bias, block_q=16, block_k=16)
    ref = fa_ref.attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), bias).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_attention_ragged_padding(key):
    """S not divisible by block => wrapper pads and un-pads correctly."""
    B, S, H, hd = 2, 37, 2, 16
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    bias = jnp.zeros((B, S, S))
    out = fa_ops.flash_attention(q, k, v, bias, block_q=16, block_k=16)
    ref = fa_ref.attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), bias).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("B,N,K", [(1, 16, 32), (3, 40, 100),
                                   (2, 64, 257), (1, 7, 1000)])
@pytest.mark.parametrize("version", [1, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dndm_update_sweep(B, N, K, version, dtype, key):
    ks = jax.random.split(key, 3)
    logits = jax.random.normal(ks[0], (B, N, K), dtype)
    x = jax.random.randint(ks[1], (B, N), 0, K)
    tau = jax.random.randint(ks[2], (B, N), 1, 20)
    for t in (1, 5, 19):
        out = dndm_ops.dndm_update(logits, x, tau, t, version=version,
                                   block_n=16, block_v=64)
        ref = dndm_ref.dndm_update_ref(logits, x, tau,
                                       jnp.asarray([t]), version=version)
        assert (np.asarray(out) == np.asarray(ref)).all()


@pytest.mark.parametrize("B,N,K", [(1, 16, 32), (3, 40, 100),
                                   (2, 64, 257), (1, 7, 1000)])
@pytest.mark.parametrize("gumbel", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_scores_sweep(B, N, K, gumbel, dtype, key):
    """Streaming (token, score) kernel vs oracle: tokens bitwise, scores
    allclose (online logsumexp), masked + temperature + both dtypes."""
    ks = jax.random.split(key, 2)
    logits = jax.random.normal(ks[0], (B, N, K), dtype)
    mask = jnp.where(jnp.arange(K) == K - 1, -1e9, 0.0)
    g = jax.random.gumbel(ks[1], (B, N, K), jnp.float32) if gumbel else None
    tok, score = ds_ops.decode_scores(logits, mask=mask, gumbel=g,
                                      temperature=0.7, block_n=16,
                                      block_v=64)
    rt, rs = ds_ref.decode_scores_ref(logits, mask=mask, gumbel=g,
                                      temperature=0.7)
    assert (np.asarray(tok) == np.asarray(rt)).all()
    np.testing.assert_allclose(np.asarray(score), np.asarray(rs),
                               atol=2e-5, rtol=2e-5)
    # rank key sanity: scores are log-probs of the chosen token
    assert (np.asarray(score) <= 1e-6).all()
    assert not (np.asarray(tok) == K - 1).any()   # masked id never decoded


@pytest.mark.parametrize("B,S,H,P,Nst,chunk", [
    (1, 16, 1, 4, 8, 4), (2, 48, 3, 8, 16, 16), (1, 64, 2, 16, 8, 32),
    (2, 33, 2, 8, 8, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(B, S, H, P, Nst, chunk, dtype, key):
    ks = jax.random.split(key, 5)
    x = (jax.random.normal(ks[0], (B, S, H, P)) * 0.5).astype(dtype)
    dtv = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = (jax.random.normal(ks[3], (B, S, Nst)) * 0.3).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, S, Nst)) * 0.3).astype(dtype)
    y_seq, _ = ssd_ref.ssd_sequential_ref(x, dtv, A, Bm, Cm)
    y_kern, _ = ssd_ops.ssd_scan(x, dtv, A, Bm, Cm, chunk=chunk)
    tol = 3e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y_kern, np.float32),
                               np.asarray(y_seq, np.float32),
                               atol=tol, rtol=tol)


def test_ssd_chunked_ref_matches_sequential(key):
    """The model's chunked implementation == the exact recurrence."""
    B, S, H, P, Nst = 2, 40, 2, 8, 16
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dtv = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, Nst)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, Nst)) * 0.3
    y_seq, s_seq = ssd_ref.ssd_sequential_ref(x, dtv, A, Bm, Cm)
    for chunk in (5, 8, 40, 64):
        y_c, s_c = ssd_ref.ssd_chunked_ref(x, dtv, A, Bm, Cm, chunk)
        np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_seq),
                                   atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_seq),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("N,K,block_n,block_v,want", [
    (256, 28, 256, 1024, (256, 128)),      # whole padded axes
    (256, 28, 8, 64, (128, 128)),          # small caller blocks rounded up
    (1000, 32000, 256, 1024, (256, 1024)),
    (200, 300, 100, 200, (128, 256)),      # N split into 128-wide blocks
    (7, 1000, 16, 64, (8, 128)),
])
def test_block_sizes_compiled_mode_follows_tiling(N, K, block_n, block_v,
                                                  want):
    """Compiled Mosaic: token block a multiple of 128 or the whole padded
    axis, vocab block a multiple of 128, whatever the caller passes."""
    bn, bkv = dndm_ops.block_sizes(N, K, block_n, block_v, interpret=False)
    assert (bn, bkv) == want
    n8 = -(-N // 8) * 8
    assert bn % 128 == 0 or bn == n8
    assert bkv % 128 == 0


def test_block_sizes_interpret_mode_keeps_caller_blocks():
    assert dndm_ops.block_sizes(64, 257, 16, 64, interpret=True) == (16, 64)
