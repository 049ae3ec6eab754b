"""The grouped matmul of the expert layer (``kernels/moe_gmm``): the TPU
kernel in interpret mode and the portable path against the oracle, with
empty groups, groups that are not a multiple of the row tile, and more
buffer rows than routed pairs (rows past them are poisoned with NaN and
must reach no output row of a group); each as one matmul and as the
fused SwiGLU of two.  The kernel reading one layer of a stack of
weights, and the gradient through the kernel path, which Pallas cannot
take itself (its custom VJP against the oracle's)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe_gmm import gmm, gmm_ref
from repro.kernels.moe_gmm.kernel import moe_gmm
from repro.kernels.moe_gmm.ops import kernel_gmm

TM = 16

CASES = [
    # (group sizes, buffer rows)
    ([16, 32, 16, 0], 64),          # tile-aligned, one empty group
    ([5, 0, 17, 30], 64),           # unaligned, 12 rows past the pairs
    ([0, 0, 0, 0], 32),             # nothing routed here
    ([3, 60, 1, 0], 64),            # a group spanning four tiles
    ([0, 0, 0, 9], 48),             # only the last group
]


def _operands(sizes, m, dtype, swiglu=False, k=32, n=40, seed=0):
    key = jax.random.PRNGKey(seed)
    lhs = jax.random.normal(key, (m, k), jnp.float32)
    rows = jnp.arange(m)[:, None] < sum(sizes)
    lhs = jnp.where(rows, lhs, jnp.nan).astype(dtype)
    rhs, up = (jax.random.normal(jax.random.fold_in(key, i),
                                 (len(sizes), k, n)).astype(dtype)
               for i in (1, 2))
    return lhs, rhs, jnp.asarray(sizes, jnp.int32), up if swiglu else None


def _tol(dtype):
    return dict(atol=1e-4, rtol=1e-4) if dtype == jnp.float32 else dict(
        atol=0.1, rtol=0.02)


def _check(out, lhs, rhs, sizes, up, dtype):
    total = int(sizes.sum())
    want = gmm_ref(jnp.nan_to_num(lhs), rhs, sizes, up)
    assert out.shape == want.shape and out.dtype == lhs.dtype
    got = np.asarray(out[:total], np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want[:total], np.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("swiglu", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sizes,m", CASES)
def test_kernel_interpret_matches_ref(sizes, m, dtype, swiglu):
    lhs, rhs, gs, up = _operands(sizes, m, dtype, swiglu)
    out = moe_gmm(lhs, rhs, gs, up, tm=TM, interpret=True)
    _check(out, lhs, rhs, gs, up, dtype)


@pytest.mark.parametrize("swiglu", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sizes,m", CASES)
def test_portable_path_matches_ref(sizes, m, dtype, swiglu):
    lhs, rhs, gs, up = _operands(sizes, m, dtype, swiglu, seed=1)
    _check(gmm(lhs, rhs, gs, up), lhs, rhs, gs, up, dtype)


def test_kernel_refuses_rows_off_the_tile():
    lhs, rhs, gs, _ = _operands([5, 5, 5, 5], 20, jnp.float32)
    with pytest.raises(ValueError, match="tile"):
        moe_gmm(lhs, rhs, gs, tm=TM, interpret=True)


def test_ref_leaves_rows_past_the_pairs_at_zero():
    lhs, rhs, gs, _ = _operands([2, 3, 0, 1], 16, jnp.float32)
    out = gmm_ref(jnp.nan_to_num(lhs), rhs, gs)
    assert float(jnp.abs(out[6:]).max()) == 0.0
    np.testing.assert_allclose(np.asarray(out[2]), np.asarray(lhs[2] @
                                                               rhs[1]),
                               atol=1e-4)


@pytest.mark.parametrize("swiglu", [False, True])
@pytest.mark.parametrize("sizes,m", CASES[:2])
def test_kernel_reads_one_layer_of_a_stack(sizes, m, swiglu):
    """Given a stack (L, G, K, N) and a layer index the kernel computes
    with that layer's weights, as given the slice itself."""
    lhs, rhs, gs, up = _operands(sizes, m, jnp.float32, swiglu, seed=2)
    stack = jnp.stack([rhs * 3.0, rhs, rhs * -2.0])
    up_stack = None if up is None else jnp.stack([up * 0.5, up, up])
    out = moe_gmm(lhs, stack, gs, up_stack, jnp.int32(1), tm=TM,
                  interpret=True)
    _check(out, lhs, rhs, gs, up, jnp.float32)


def _loss(fn, lhs, rhs, up, sizes, cot):
    total = int(sizes.sum())
    return lambda a, w, u: jnp.sum(
        fn(a, w, sizes, u)[:total].astype(jnp.float32) * cot[:total])


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("swiglu", [False, True])
@pytest.mark.parametrize("sizes,m", [CASES[1], CASES[3]])
def test_gradient_through_the_kernel_path(sizes, m, swiglu, stacked):
    """``kernel_gmm`` (the kernel in the interpreter) has the oracle's
    gradient in the operands and both weights; with a stack of weights
    the gradient goes to the slice passed beside it.  Buffer rows past
    the pairs get a zero gradient."""
    lhs, rhs, gs, up = _operands(sizes, m, jnp.float32, swiglu, seed=3)
    lhs = jnp.nan_to_num(lhs)
    cot = jax.random.normal(jax.random.PRNGKey(4), (m, rhs.shape[2]))

    def kernel(a, w, sizes, u):
        pack = None
        if stacked:
            pack = (jnp.stack([w * 0 + 7.0, w]),
                    None if u is None else jnp.stack([u * 0 + 7.0, u]),
                    jnp.int32(1))
        return kernel_gmm(a, w, sizes, u, pack, True)

    args = (lhs, rhs, up)
    argnums = (0, 1, 2) if swiglu else (0, 1)
    got = jax.grad(_loss(kernel, *args, gs, cot), argnums)(*args)
    want = jax.grad(_loss(gmm_ref, *args, gs, cot), argnums)(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-4, rtol=1e-4)
    assert float(jnp.abs(got[0][sum(sizes):]).max(initial=0.0)) == 0.0
