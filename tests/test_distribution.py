"""Sharding rules (pure functions of mesh metadata; no devices needed)
and the transition-time laws."""
import jax
import numpy as np
from jax.sharding import PartitionSpec as P

import repro.configs as C
from repro.launch.sharding import ShardingPolicy, param_spec, cache_spec


class FakeMesh:
    """Mesh metadata stand-in (axis sizes only; rules never need devices)."""

    def __init__(self, shape: dict):
        self.shape = shape
        self.axis_names = tuple(shape)


MESH = FakeMesh({"data": 16, "model": 16})
MESH3 = FakeMesh({"pod": 2, "data": 16, "model": 16})
POL = ShardingPolicy()
CFG = C.get("mixtral-8x7b")


def test_param_rules_tp():
    assert param_spec("unit/b0/attn/wq", (32, 4096, 4096), MESH, POL,
                      CFG) == P(None, None, "model")
    assert param_spec("unit/b0/attn/wo", (32, 4096, 4096), MESH, POL,
                      CFG) == P(None, "model")
    assert param_spec("unit/b0/mlp/down", (32, 14336, 4096), MESH, POL,
                      CFG) == P(None, "model")
    assert param_spec("embed", (32000, 4096), MESH, POL, CFG) == P("model")
    assert param_spec("head", (4096, 32000), MESH, POL, CFG) == \
        P(None, "model")
    assert param_spec("ln_f/scale", (4096,), MESH, POL, CFG) == P()


def test_param_rules_moe_fallback():
    # mixtral: 8 experts, model=16 => not divisible => ff tensor parallel
    assert param_spec("unit/b0/moe/up", (32, 8, 4096, 14336), MESH, POL,
                      CFG) == P(None, None, None, "model")
    assert param_spec("unit/b0/moe/down", (32, 8, 14336, 4096), MESH, POL,
                      CFG) == P(None, None, "model")
    # llama4: 128 experts => expert-parallel
    cfg4 = C.get("llama4-maverick-400b-a17b")
    assert param_spec("unit/b0/moe/up", (48, 128, 5120, 8192), MESH, POL,
                      cfg4) == P(None, "model")


def _norm(spec):
    """Normalize PartitionSpec entries to tuples, drop trailing Nones."""
    out = []
    for e in spec:
        out.append(tuple(e) if isinstance(e, (tuple, list))
                   else ((e,) if e else None))
    while out and out[-1] is None:
        out.pop()
    return tuple(out)


def test_param_rules_nondivisible_replicates():
    # a dim not divisible by the model axis must replicate
    spec = param_spec("unit/b0/attn/wq", (30, 3072, 100), MESH, POL, CFG)
    assert _norm(spec) == ()


def test_cache_specs():
    # decode_32k: B=128 shardable on data
    spec = cache_spec(MESH, (32, 128, 32768, 8, 128), 128, POL, "kv")
    assert _norm(spec)[1] == ("data",)
    # long_500k: B=1 -> context parallelism on seq
    spec = cache_spec(MESH, (9, 1, 524288, 32, 80), 1, POL, "kv")
    assert _norm(spec)[2] == ("data",)
    # multi-pod batch axes
    spec = cache_spec(MESH3, (32, 128, 32768, 8, 128), 128, POL, "kv")
    assert _norm(spec)[1] == ("pod", "data")


# ---------------------------------------------------------------------
# Transition-law conformance (ISSUE 8): the sampled transition-time
# marginals must match the analytic laws of the paper.
#
# Tolerance rationale (all seeds fixed, so every run sees the same
# draws — thresholds guard against *implementation* drift, not luck):
#
# * chi-square: with the law correct the statistic is asymptotically
#   chi2 with dof = (#bins - 1), mean dof and sd sqrt(2 dof).  We accept
#   up to dof + 4 sd — one-sided false-alarm ~3e-5 were the seed free —
#   while an off-by-one in the time indexing (mass shifted by one bin)
#   moves the statistic by O(n/T), orders of magnitude past it.
# * KS: the Kolmogorov critical value is sqrt(-ln(a/2)/2)/sqrt(n);
#   a = 1e-4 gives 2.22/sqrt(n).  We add 2e-3 slack for the trapezoid
#   quadrature error of the scipy-free _beta_cdf oracle.
# ---------------------------------------------------------------------
from repro.core import schedules, transition
from repro.core.transition import _beta_cdf


def _chi_square(counts: np.ndarray, expected: np.ndarray,
                min_expected: float = 8.0) -> tuple[float, int]:
    """Pearson statistic with small-expectation bins pooled (the chi2
    approximation needs every expected count above a handful)."""
    stat, dof, o_acc, e_acc = 0.0, 0, 0.0, 0.0
    for o, e in zip(counts, expected):
        o_acc += o
        e_acc += e
        if e_acc >= min_expected:
            stat += (o_acc - e_acc) ** 2 / e_acc
            dof += 1
            o_acc = e_acc = 0.0
    if e_acc > 0:       # fold the remainder into the last pooled bin
        stat += (o_acc - e_acc) ** 2 / max(e_acc, min_expected)
        dof += 1
    return stat, dof - 1


def test_thm36_finite_t_marginal_chi_square():
    """Theorem 3.6: P(tau = t) = alpha_{t-1} - alpha_t.  The categorical
    sampler must reproduce exactly the schedule's transition_probs."""
    T, n = 50, 20_000
    dist = transition.from_schedule(schedules.linear(T))
    tau = np.asarray(dist.sample(jax.random.PRNGKey(0), (n,)))
    assert tau.min() >= 1 and tau.max() <= T
    counts = np.bincount(tau, minlength=T + 1)[1:].astype(float)
    stat, dof = _chi_square(counts, n * dist.probs)
    assert stat < dof + 4 * np.sqrt(2 * dof), (stat, dof)


def test_beta_approx_marginal_chi_square():
    """beta_approx discretizes Beta(a, b) by CDF differencing at the bin
    edges k/T (paper §3.2) and samples the resulting categorical: the
    analytic bin masses are F(k/T) - F((k-1)/T), recomputed here from
    the quadrature CDF independently of the TransitionDist internals."""
    T, a, b, n = 40, 15.0, 7.0, 20_000
    dist = transition.beta_approx(T, a, b)
    tau = np.asarray(dist.sample(jax.random.PRNGKey(1), (n,)))
    assert tau.min() >= 1 and tau.max() <= T
    counts = np.bincount(tau, minlength=T + 1)[1:].astype(float)
    expected = np.diff(_beta_cdf(np.arange(T + 1) / T, a, b))
    stat, dof = _chi_square(counts, n * expected)
    assert stat < dof + 4 * np.sqrt(2 * dof), (stat, dof)


def test_continuous_beta_ks():
    """DNDM-C timestamps: sample_continuous ~ Beta(a, b) on (0, 1]."""
    a, b, n = 15.0, 7.0, 4_000
    cdist = transition.beta_continuous(a, b)
    x = np.sort(np.asarray(
        cdist.sample_continuous(jax.random.PRNGKey(2), (n,))))
    F = _beta_cdf(x, a, b)
    ecdf_hi = np.arange(1, n + 1) / n
    ks = max(np.abs(ecdf_hi - F).max(), np.abs(F - (ecdf_hi - 1 / n)).max())
    assert ks < 2.22 / np.sqrt(n) + 2e-3, ks


def test_continuous_from_discrete_law_ks():
    """A probs-backed law samples continuous times by inverse-CDF on the
    grid plus uniform within-bin jitter: the CDF is the piecewise-linear
    interpolant of cumsum(probs) at the bin edges t/T."""
    T, n = 50, 4_000
    dist = transition.from_schedule(schedules.cosine(T))
    x = np.sort(np.asarray(
        dist.sample_continuous(jax.random.PRNGKey(3), (n,))))
    knots_x = np.arange(T + 1) / T
    knots_F = np.concatenate([[0.0], np.cumsum(dist.probs)])
    F = np.interp(x, knots_x, knots_F)
    ecdf_hi = np.arange(1, n + 1) / n
    ks = max(np.abs(ecdf_hi - F).max(), np.abs(F - (ecdf_hi - 1 / n)).max())
    assert ks < 2.22 / np.sqrt(n) + 2e-3, ks
