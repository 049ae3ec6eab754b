"""Model zoo behaviour: decode==forward consistency, bidirectional mode,
MoE dispatch invariants, attention impl equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep (requirements-dev.txt)
from hypothesis import given, settings, strategies as st

from repro.models import Model, ModelConfig
from repro.models import attention, moe


def tiny(pattern, **kw):
    base = dict(name="t", arch_type="x", n_layers=len(pattern), d_model=64,
                n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=50,
                block_pattern=pattern, ssm_state=16, ssm_head_dim=32,
                ssd_chunk=8, lstm_heads=2, sliding_window=8)
    base.update(kw)
    return ModelConfig(**base)


FAMILIES = {
    "dense": tiny(("attn",) * 2),
    "swa": tiny(("swa",) * 2),
    "moe": tiny(("moe",) * 2, n_experts=4, experts_per_token=2),
    "mamba": tiny(("mamba2",) * 2),
    "xlstm": tiny(("mlstm", "slstm")),
    "zamba": tiny(("mamba2", "shared_attn") * 2),
}


@pytest.mark.parametrize("fam", list(FAMILIES))
def test_decode_matches_forward(fam, key):
    cfg = FAMILIES[fam]
    m = Model(cfg)
    p = m.init(key)
    tok = jax.random.randint(jax.random.fold_in(key, 1), (2, 10), 0, 50)
    full, _ = m.forward(p, tok, None, causal=True)
    cache = m.init_cache(2, 10)
    step = jax.jit(m.decode_step)
    outs = []
    for i in range(10):
        lg, cache = step(p, tok[:, i:i + 1], cache, jnp.asarray(i))
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, 1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               atol=2e-4, rtol=2e-3)


@pytest.mark.parametrize("fam", list(FAMILIES))
def test_bidirectional_uses_future_context(fam, key):
    """In denoiser mode, changing a future token changes past logits."""
    cfg = FAMILIES[fam]
    m = Model(cfg)
    p = m.init(key)
    tok = jax.random.randint(jax.random.fold_in(key, 2), (1, 10), 0, 50)
    tok2 = tok.at[0, -1].set((tok[0, -1] + 1) % 50)
    a, _ = m.forward(p, tok, None, causal=False)
    b, _ = m.forward(p, tok2, None, causal=False)
    assert float(jnp.abs(a[0, 0] - b[0, 0]).max()) > 1e-6
    # and causal mode must NOT leak the future
    a, _ = m.forward(p, tok, None, causal=True)
    b, _ = m.forward(p, tok2, None, causal=True)
    assert float(jnp.abs(a[0, 0] - b[0, 0]).max()) < 1e-6


def test_sliding_window_locality(key):
    """SWA: tokens beyond the window cannot influence the query."""
    cfg = tiny(("swa",) * 1, sliding_window=4)
    m = Model(cfg)
    p = m.init(key)
    tok = jax.random.randint(jax.random.fold_in(key, 3), (1, 16), 0, 50)
    tok2 = tok.at[0, 0].set((tok[0, 0] + 1) % 50)
    a, _ = m.forward(p, tok, None, causal=True)
    b, _ = m.forward(p, tok2, None, causal=True)
    # position 15 is > 4 steps away from position 0
    assert float(jnp.abs(a[0, 15] - b[0, 15]).max()) < 1e-6
    assert float(jnp.abs(a[0, 2] - b[0, 2]).max()) > 1e-7


MASKS = {
    "causal": (("attn",) * 2, {}, True),
    "bidirectional": (("attn",) * 2, {}, False),
    "window4": (("swa",) * 2, {"sliding_window": 4}, False),
    "block_causal4": (("attn",) * 2, {"block_causal": 4}, False),
}


@pytest.mark.parametrize("mask", list(MASKS))
def test_attention_impl_equivalence(mask, key):
    """einsum / blocked / pallas give the same attention output under
    each mask the denoisers run."""
    pattern, extra, causal = MASKS[mask]
    outs = {}
    tok = jax.random.randint(jax.random.fold_in(key, 4), (2, 24), 0, 50)
    for impl in ("einsum", "blocked", "pallas"):
        cfg = tiny(pattern, attn_impl=impl, attn_block_q=8,
                   attn_block_k=8, **extra)
        m = Model(cfg)
        p = m.init(jax.random.PRNGKey(11))
        logits, _ = m.forward(p, tok, None, causal=causal)
        outs[impl] = np.asarray(logits)
    np.testing.assert_allclose(outs["einsum"], outs["blocked"],
                               atol=3e-4, rtol=3e-3)
    np.testing.assert_allclose(outs["einsum"], outs["pallas"],
                               atol=3e-4, rtol=3e-3)


@pytest.mark.parametrize("experts,top_k,held,offset", [
    (4, 2, 0, 0), (8, 2, 0, 0), (16, 1, 0, 0), (16, 8, 4, 0),
    (16, 8, 4, 12)])
def test_moe_dispatch_is_weighted_permutation(experts, top_k, held, offset,
                                              key):
    """The dropless layer == the dense per-token mix of the held experts'
    outputs by their gates (held 0: every expert)."""
    cfg = tiny(("moe",), n_experts=experts, experts_per_token=top_k,
               held_experts=held, expert_offset=offset)
    params = moe.init(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 5), (2, 6, 64))
    y, aux = moe.apply(params, x, cfg)
    # dense reference: run every held expert on every token, mix by gates
    logits = (x.reshape(-1, 64) @ params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    gv, ei = jax.lax.top_k(probs, top_k)
    gv = gv / gv.sum(-1, keepdims=True)
    xt = x.reshape(-1, 64)
    h = jnp.einsum("td,edf->tef", xt, params["gate"])
    hu = jnp.einsum("td,edf->tef", xt, params["up"])
    act = jax.nn.silu(h) * hu
    out_all = jnp.einsum("tef,efd->ted", act, params["down"])
    ref = jnp.zeros_like(xt)
    for kk in range(top_k):
        local = ei[:, kk] - offset
        mine = (local >= 0) & (local < cfg.n_held)
        sel = jnp.take_along_axis(
            out_all, jnp.clip(local, 0, cfg.n_held - 1)[:, None, None],
            axis=1)[:, 0]
        ref = ref + jnp.where(mine[:, None], sel * gv[:, kk][:, None], 0)
    np.testing.assert_allclose(np.asarray(y.reshape(-1, 64)),
                               np.asarray(ref), atol=1e-4, rtol=1e-3)


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=8, deadline=None)
def test_moe_never_nan(seed):
    cfg = tiny(("moe",), n_experts=4, experts_per_token=2)
    k = jax.random.PRNGKey(seed)
    params = moe.init(k, cfg)
    x = jax.random.normal(jax.random.fold_in(k, 1), (1, 8, 64)) * 3
    y, aux = moe.apply(params, x, cfg)
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(float(aux["load_balance"]))


def test_frontend_fusion(key):
    cfg = tiny(("attn",) * 2, frontend="audio", frontend_tokens=4)
    m = Model(cfg)
    p = m.init(key)
    tok = jax.random.randint(jax.random.fold_in(key, 6), (2, 12), 0, 50)
    fe = jax.random.normal(jax.random.fold_in(key, 7), (2, 4, 64))
    a, _ = m.forward(p, tok, None, fe, causal=False)
    fe2 = fe.at[0, 0].add(1.0)
    b, _ = m.forward(p, tok, None, fe2, causal=False)
    assert float(jnp.abs(a - b).max()) > 1e-6   # embeddings actually used
    assert a.shape == (2, 12, 50)


def test_ring_buffer_decode_beyond_window(key):
    """SWA decode past the physical cache length stays consistent with a
    full forward (ring buffer correctness)."""
    W = 4
    cfg = tiny(("swa",), sliding_window=W)
    m = Model(cfg)
    p = m.init(key)
    S = 12
    tok = jax.random.randint(jax.random.fold_in(key, 8), (1, S), 0, 50)
    full, _ = m.forward(p, tok, None, causal=True)
    cache = m.init_cache(1, W)      # physical cache = window only
    outs = []
    for i in range(S):
        lg, cache = m.decode_step(p, tok[:, i:i + 1], cache, jnp.asarray(i))
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, 1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               atol=2e-4, rtol=2e-3)


PUBLISHED = {     # arch: (total, active or None, relative tolerance)
    "tinyllama-1.1b": (1.1e9, None, 0.05),
    "mixtral-8x7b": (46.7e9, 12.9e9, 0.10),
    "phi3-mini-3.8b": (3.8e9, None, 0.10),
    "deepseek-7b": (6.9e9, None, 0.10),
    "chameleon-34b": (34e9, None, 0.10),
    "starcoder2-3b": (3.0e9, None, 0.10),
    "sdar-30b-a3b": (30.5e9, 3.3e9, 0.10),
    "dndm-text8": (114_488_064, None, 0.0),
}
OFF_PUBLISHED = {  # arch: (total, active or None, what the preset builds)
    "llama4-maverick-400b-a17b": (
        400e9, 17e9, "778.3B total / 11.2B active: all 48 layers MoE, no "
        "shared expert (the docstring cites Scout)"),
    "zamba2-2.7b": (2.7e9, None, "2.08B"),
    "xlstm-350m": (0.35e9, None, "0.51B"),
    "musicgen-large": (3.3e9, None, "2.43B"),
}


@pytest.mark.parametrize("arch", list(PUBLISHED) + [
    pytest.param(arch, marks=pytest.mark.xfail(
        strict=True, reason=f"preset off its published size: builds "
        f"{OFF_PUBLISHED[arch][2]}"))
    for arch in OFF_PUBLISHED])
def test_param_count_matches_published(arch):
    """A preset's parameter count (total and, for MoE, active: the router
    and the top-k experts' share) against the published one."""
    import repro.configs as C
    total, active, tol = (PUBLISHED[arch] if arch in PUBLISHED
                          else OFF_PUBLISHED[arch][:2] + (0.10,))
    m = Model(C.get(arch))
    layout = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    assert abs(m.param_count(layout) - total) <= tol * total
    if active is not None:
        assert abs(m.active_param_count(layout) - active) <= tol * active


def test_mlstm_chunked_matches_parallel(key):
    """§Perf it1 (xlstm): chunkwise mLSTM == full parallel form."""
    from repro.models import xlstm
    cfg = tiny(("mlstm",), lstm_heads=2)
    p = xlstm.mlstm_init(key, cfg)
    u = jax.random.normal(jax.random.fold_in(key, 10), (2, 37, 64)) * 0.5
    full = xlstm.mlstm_apply(p, u, cfg)
    for chunk in (8, 16):
        c = xlstm.mlstm_apply(p, u, cfg.replace(mlstm_chunk=chunk))
        np.testing.assert_allclose(np.asarray(full), np.asarray(c),
                                   atol=5e-5, rtol=5e-4)
