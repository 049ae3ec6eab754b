"""The routing's row movement (``kernels/moe_route``): the dispatch and
combine kernels, in the TPU interpreter, against the XLA gather and
combine they replace, over routings that hold no pair, every pair, one
expert taking every pair, a random share, and a number of pairs that is
not a multiple of the row tile; rows past the held pairs poisoned with
NaN never reach a token's output.  In the expert layer: the gradient
through the kernels equals the XLA path's, ``held_share`` is the held
pairs' share, and a layer that holds every expert never calls them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import moe_route as route
from repro.kernels.moe_gmm import ops as gmm_ops
from repro.kernels.moe_route.kernel import moe_combine, moe_dispatch
from repro.models import ModelConfig, moe

INTERPRET = pltpu.InterpretParams()
E, H = 16, 4                     # experts routed to, held (0..H-1)

ROUTINGS = {
    # name: (T, k, expert of each pair from a key)
    "none_held": (96, 4, lambda key, n: jax.random.randint(key, (n,), H, E)),
    "all_held": (96, 4, lambda key, n: jax.random.randint(key, (n,), 0, H)),
    "one_expert": (96, 4, lambda key, n: jnp.full((n,), 2)),
    "random": (96, 4, lambda key, n: jax.random.randint(key, (n,), 0, E)),
    "off_tile": (37, 2, lambda key, n: jax.random.randint(key, (n,), 0, E)),
}


def _routing(name, dtype, seed=0, d=256):
    T, k, experts = ROUTINGS[name]
    key = jax.random.PRNGKey(seed)
    e = experts(jax.random.fold_in(key, 1), T * k)
    held = e < H
    order = jnp.argsort(jnp.where(held, e, H), stable=True).astype(
        jnp.int32)
    x = jax.random.normal(key, (T, d)).astype(dtype)
    gates = jax.random.uniform(jax.random.fold_in(key, 2), (T, k))
    return x, order, held, held.sum(dtype=jnp.int32), gates, k


def _tol(dtype):
    return dict(atol=1e-5, rtol=1e-5) if dtype == jnp.float32 else dict(
        atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", list(ROUTINGS))
def test_dispatch_matches_the_gather(name, dtype):
    x, order, held, count, _, k = _routing(name, dtype)
    h = route.dispatch(x, order, count, k, INTERPRET)
    n = int(count)
    assert h.shape == (order.shape[0], x.shape[1]) and h.dtype == x.dtype
    np.testing.assert_array_equal(
        np.asarray(h[:n], np.float32),
        np.asarray(route.dispatch_ref(x, order, k)[:n], np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", list(ROUTINGS))
def test_combine_matches_the_masked_sum(name, dtype):
    """The buffer's rows past the held pairs hold NaN, as unwritten rows
    may: no token's output sees them."""
    x, order, held, count, gates, k = _routing(name, dtype, seed=1)
    rows = jnp.arange(order.shape[0])[:, None]
    out = jnp.where(rows < count, route.dispatch_ref(x, order, k) * 0.5,
                    jnp.nan).astype(dtype)
    y = route.combine(out, gates, order, held, count, dtype, INTERPRET)
    want = route.combine_ref(out, route.slots(order), held, gates, dtype)
    assert y.shape == want.shape and y.dtype == dtype
    got = np.asarray(y, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want, np.float32),
                               **_tol(dtype))
    if name == "none_held":
        assert not got.any()


def test_dispatch_then_combine_through_unwritten_rows():
    """The combine reads the dispatch's own buffer, whose rows past the
    held pairs were never written (NaN in the interpreter)."""
    x, order, held, count, gates, k = _routing("random", jnp.float32, 2)
    h = route.dispatch(x, order, count, k, INTERPRET)
    assert np.isnan(np.asarray(h[-1])).all()           # a tile past them
    y = route.combine(h, gates, order, held, count, jnp.float32, INTERPRET)
    want = route.combine_ref(route.dispatch_ref(x, order, k),
                             route.slots(order), held, gates, jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5,
                               rtol=1e-5)


def test_kernels_refuse_rows_off_the_tile():
    x, order, held, count, gates, k = _routing("off_tile", jnp.float32)
    with pytest.raises(ValueError, match="tile"):
        moe_dispatch(x, order, count, k=k, interpret=INTERPRET)
    with pytest.raises(ValueError, match="tile"):
        moe_combine(x[:74], order, gates.reshape(-1), count, t=37,
                    dtype=jnp.float32, interpret=INTERPRET)


def _layer(held_experts=H, n_experts=E):
    return ModelConfig(name="t", arch_type="x", n_layers=1, d_model=128,
                       n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=50,
                       block_pattern=("moe",), n_experts=n_experts,
                       experts_per_token=4, held_experts=held_experts,
                       dtype="float32")


def _calls(monkeypatch):
    """Steer the layer to the kernels (as on a TPU) and count their calls."""
    calls = []
    monkeypatch.setattr(gmm_ops, "use_kernel", lambda: True)
    for name in ("dispatch", "combine"):
        op = getattr(route, name)

        def counted(*a, _op=op, _name=name):
            calls.append(_name)
            return _op(*a)
        monkeypatch.setattr(route, name, counted)
    return calls


def test_layer_gradient_through_the_kernels(monkeypatch):
    cfg = _layer()
    p = moe.init(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 48, 128))

    def loss(p, x):
        y, aux = moe.apply(p, x, cfg)
        return jnp.sum(jnp.sin(y)) + aux["load_balance"]

    want_v, want_g = jax.value_and_grad(loss, (0, 1))(p, x)
    calls = _calls(monkeypatch)
    got_v, got_g = jax.value_and_grad(loss, (0, 1))(p, x)
    assert sorted(set(calls)) == ["combine", "dispatch"]
    np.testing.assert_allclose(float(got_v), float(want_v), rtol=1e-5)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-5, rtol=1e-4)
    assert float(jnp.abs(got_g[1]).max()) > 0


def test_held_share_is_the_held_pairs_share():
    cfg = _layer()
    p = moe.init(jax.random.PRNGKey(5), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 32, 128))
    _, aux = moe.apply(p, x, cfg)
    probs = jax.nn.softmax(x.reshape(-1, 128) @ p["router"], -1)
    experts = np.asarray(jax.lax.top_k(probs, 4)[1]).ravel()
    hand = sum(int(e) < H for e in experts) / experts.size
    assert 0 < hand < 1
    assert float(aux["held_share"]) == pytest.approx(hand, abs=1e-7)
    whole = _layer(held_experts=0)
    _, aux = moe.apply(moe.init(jax.random.PRNGKey(5), whole), x, whole)
    assert float(aux["held_share"]) == 1.0


@pytest.mark.parametrize("held_experts,used", [(0, False), (H, True)])
def test_kernels_only_where_a_share_is_held(monkeypatch, held_experts,
                                            used):
    """A layer that holds every expert lowers with XLA's gathers even
    where the kernels would run; one that holds a share calls both."""
    cfg = _layer(held_experts)
    p = moe.init(jax.random.PRNGKey(7), cfg)
    x = jax.ShapeDtypeStruct((2, 32, 128), jnp.float32)
    calls = _calls(monkeypatch)
    text = jax.jit(lambda p, x: moe.apply(p, x, cfg)[0]).lower(
        p, x).as_text()
    assert sorted(set(calls)) == (["combine", "dispatch"] if used else [])
    for name in ("moe_dispatch", "moe_combine"):
        assert (name in text) == used
