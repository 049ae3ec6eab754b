"""SDAR (Qwen3-MoE layer) on one chip's expert share, at a CPU size: the
program against the plain float32 reference, shares that add up to the
uncut layer, dropless routing, the block-causal mask, the gradient with
the experts in the grouped-matmul kernel, and dense configurations left
as they were.

The reduced model: d 256, 4 query heads over 1 KV head at head_dim 128
(q is 512 wide against a residual of 256), q/k norms, block length 4, 8
routed experts of width 64, top-2, 4 held from offset 4, no time input,
float32, 2 layers, weights from a seed (``perfbench/weights.py``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import cell, weights
from perfbench.refs import sdar_moe_denoiser as ref
from repro.kernels.moe_gmm import ops as gmm_ops
from repro.models import Model, moe

V = 97


def small_conf(**kw) -> dict:
    conf = dict(cell.load_json(cell.HERE / "configs"
                               / "sdar-30b-a3b-ep8.json"),
                num_hidden_layers=2, hidden_size=256, num_attention_heads=4,
                num_key_value_heads=1, head_dim=128,
                moe_intermediate_size=64, num_experts_published=8,
                num_experts_per_tok=2, num_experts=4, expert_offset=4,
                vocab_size=V, mask_id=V - 1, torch_dtype="float32")
    conf.update(kw)
    return conf


def built(conf: dict, seed: int = 3):
    model = Model(cell.model_config(conf))
    return model, weights.make(model, seed, conf["num_hidden_layers"])


def tokens(seed: int, shape=(2, 16)):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, V)


def program_logits(model, params, tok):
    logits, _ = model.forward(params, tok, jnp.zeros(tok.shape[0]),
                              causal=False)
    return np.asarray(logits)


def test_small_config_is_the_share_asked_for():
    cfg = cell.model_config(small_conf())
    assert (cfg.hd, cfg.n_heads * cfg.hd, cfg.d_model) == (128, 512, 256)
    assert (cfg.n_experts, cfg.n_held, cfg.expert_offset) == (8, 4, 4)
    assert cfg.qk_norm and cfg.block_causal == 4
    assert not cfg.time_conditioning


# On the CPU both sides compute in float32 with float32 matmuls; they
# differ only in the order of summation (the grouped matmul against the
# reference's every-expert einsum, the attention's bias against a -inf
# mask), about 1e-6 of logits of size 4 (2.6e-6 seen).  The tolerance
# leaves a factor 40 above that; the same reference computed in bfloat16
# misses it by a factor 100 and more (asserted).
ATOL = 1e-4


@pytest.mark.parametrize("seed", [1, 2])
def test_logits_match_reference(seed):
    conf = small_conf()
    model, params = built(conf, seed)
    tok = tokens(seed)
    got = program_logits(model, params, tok)
    h = ref.hidden(params, tok, jnp.zeros(2), conf, mode="highest")
    want = np.asarray(ref.logits(params, h, mode="highest"))
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    hb = ref.hidden(params, tok, jnp.zeros(2), conf, mode="bfloat16")
    lower = np.asarray(ref.logits(params, hb, mode="bfloat16"))
    assert np.abs(lower - want).max() > 100 * ATOL


def _slice_experts(p: dict, lo: int, n: int) -> dict:
    return dict(p, **{k: p[k][lo:lo + n] for k in ("gate", "up", "down")})


def test_shares_add_up_to_the_uncut_layer():
    """The two chips' partial outputs (experts 0-3 and 4-7) sum to the
    layer that holds all 8; SDAR has no shared expert to count once."""
    cfg = cell.model_config(small_conf())
    whole = cfg.replace(held_experts=0, expert_offset=0)
    p = moe.init(jax.random.PRNGKey(5), whole)
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 16, 256))
    y_all, _ = moe.apply(p, x, whole)
    parts = [moe.apply(_slice_experts(p, lo, 4), x,
                       cfg.replace(expert_offset=lo))[0] for lo in (0, 4)]
    assert all(float(jnp.abs(y).max()) > 0 for y in parts)
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                               np.asarray(y_all), atol=1e-5, rtol=0)
    # and the uncut layer is the plain per-token mix of its 2 experts
    xt = x.reshape(-1, 256)
    probs = jax.nn.softmax(xt @ p["router"], -1)
    g, idx = jax.lax.top_k(probs, 2)
    g = g / g.sum(-1, keepdims=True)
    every = jnp.einsum("tef,efd->ted", jax.nn.silu(
        jnp.einsum("td,edf->tef", xt, p["gate"])) * jnp.einsum(
        "td,edf->tef", xt, p["up"]), p["down"])
    mix = sum(jnp.take_along_axis(every, idx[:, k, None, None], 1)[:, 0]
              * g[:, k, None] for k in range(2))
    np.testing.assert_allclose(np.asarray(y_all).reshape(-1, 256),
                               np.asarray(mix), atol=1e-5, rtol=0)


def test_row_output_does_not_depend_on_its_batch():
    """Adversarial routing: every token of the batch picks experts 4 and 5
    (both held), 8 times their share under uniform routing; a row's
    output is the same alone as among 4 such rows."""
    cfg = cell.model_config(small_conf())
    p = moe.init(jax.random.PRNGKey(7), cfg)
    router = jnp.zeros_like(p["router"]).at[0, 4].set(5.0).at[0, 5].set(4.0)
    p = dict(p, router=router)
    x = jax.random.normal(jax.random.PRNGKey(8), (4, 16, 256)) * 0.1
    x = x.at[..., 0].set(3.0)
    probs = jax.nn.softmax(x.reshape(-1, 256) @ router, -1)
    assert set(np.asarray(jax.lax.top_k(probs, 2)[1]).ravel()) == {4, 5}
    batch, aux = moe.apply(p, x, cfg)
    alone, _ = moe.apply(p, x[:1], cfg)
    assert float(jnp.abs(alone).max()) > 0
    np.testing.assert_allclose(np.asarray(alone), np.asarray(batch[:1]),
                               atol=1e-6, rtol=0)


def test_model_row_does_not_depend_on_its_batch():
    model, params = built(small_conf())
    tok = tokens(9, (4, 16))
    alone = program_logits(model, params, tok[:1])
    batch = program_logits(model, params, tok)
    np.testing.assert_allclose(alone, batch[:1], atol=1e-5, rtol=0)


def test_block_causal_hides_later_blocks():
    """Positions 0-7 (blocks 0 and 1) do not see a change to block 2
    (positions 8-11); positions of block 2 and after do."""
    model, params = built(small_conf())
    tok = tokens(10, (1, 16))
    changed = tok.at[0, 8:12].set((tok[0, 8:12] + 1) % (V - 1))
    a = program_logits(model, params, tok)
    b = program_logits(model, params, changed)
    np.testing.assert_array_equal(a[0, :8], b[0, :8])
    assert np.abs(a[0, 8:] - b[0, 8:]).max(-1).min() > 1e-6
    # inside a block attention runs both ways: position 8 sees 9-11
    later = tok.at[0, 11].set((tok[0, 11] + 1) % (V - 1))
    c = program_logits(model, params, later)
    assert np.abs(a[0, 8] - c[0, 8]).max() > 1e-6


def test_gradient_through_the_kernel(monkeypatch):
    """A training step's loss and gradient with the experts in the Pallas
    kernel (interpreted here; compiled on a TPU), which reads each
    layer's weights in the scan's stack, equal those through
    ``ragged_dot``, the path the CPU takes."""
    model, params = built(small_conf())
    tok = tokens(11)

    def loss(p):
        logits, aux = model.forward(p, tok, jnp.zeros(2), causal=False)
        return (jnp.mean(jax.nn.log_softmax(logits)[..., 0])
                + 0.01 * aux["load_balance"])

    want_v, want_g = jax.value_and_grad(loss)(params)
    calls = []
    kernel = gmm_ops.kernel_gmm

    def counted(*args):
        calls.append(args[4])
        return kernel(*args)

    monkeypatch.setattr(gmm_ops, "use_kernel", lambda: True)
    monkeypatch.setattr(gmm_ops, "kernel_gmm", counted)
    got_v, got_g = jax.value_and_grad(loss)(params)
    assert calls and all(c is not None for c in calls)   # stack read
    np.testing.assert_allclose(float(got_v), float(want_v), rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-5, rtol=1e-4)
    assert float(jnp.abs(got_g["unit"]["b0"]["moe"]["down"]).max()) > 0


DENSE_LEAVES = [
    "embed", "head", "ln_f/scale", "time/w1", "time/w2",
    "unit/b0/attn/wk", "unit/b0/attn/wo", "unit/b0/attn/wq",
    "unit/b0/attn/wv", "unit/b0/ln1/scale", "unit/b0/ln2/scale",
    "unit/b0/mlp/down", "unit/b0/mlp/gate", "unit/b0/mlp/up"]


@pytest.mark.parametrize("name", ["dndm-text8", "phi3-mini-3.8b"])
def test_dense_configs_unchanged(name):
    """The dense files build a ``ModelConfig`` with every new field at its
    default and the same parameter tree as before (no new leaves, so the
    seeded weights are drawn leaf for leaf as before)."""
    conf = cell.load_json(cell.HERE / "configs" / f"{name}.json")
    cfg = cell.model_config(conf)
    assert (cfg.qk_norm, cfg.block_causal, cfg.held_experts,
            cfg.expert_offset) == (False, 0, 0, 0)
    layout = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
    paths = jax.tree_util.tree_flatten_with_path(layout)[0]
    names = ["/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in paths]
    assert names == DENSE_LEAVES
    d, L = conf["hidden_size"], conf["num_hidden_layers"]
    assert layout["unit"]["b0"]["attn"]["wq"].shape == (L, d, d)
