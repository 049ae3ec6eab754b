"""Call-schedule-as-data + row-resumable sampler steps (serving substrate).

DNDM's headline structural property (Thm 3.6 / Alg. 2) is that the whole
schedule of network calls is knowable *before* sampling starts: sample
the transition-time set tau at admission and the request's unique-time
walk, its per-step PRNG keys and its x_T draw are all determined.  This
module reifies that as data:

* :class:`CallSchedule` — one request's predetermined call schedule
  (descending times, per-call key stream, tau set, x_T), produced by a
  per-method ``schedule_fn(key, rt, N)`` registered on the sampler spec.
  Every plan replays the solo sampler's ``loop.setup`` key-split
  discipline for a batch of one, so a request admitted into a rolling
  batch replays exactly the solo run's randomness.  Grid baselines
  (d3pm / rdm / mask_predict / ddim) have a data-independent times list
  but still carry their own (x_T, key stream); the static DNDM variants
  additionally carry the quantile-bucketized tau.
* batched **row steps** — jitted step functions that advance every live
  row of a rolling batch by one entry of *its own* schedule, at its own
  diffusion time (the denoiser takes per-row ``t_norm``), with its own
  per-row Gumbel/uniform/Bernoulli slab.  This is what lets
  ``ContinuousScheduler`` admit mid-flight and skip the no-op steps a
  drain batch would pay for — for *every* registered method, not just
  the DNDM family.

Bitwise parity with the solo path rests on three audited contracts:
``decode_tokens`` and ``fused_update`` share the token-selection
pre-activation (``adjust_logits`` op order, see kernels/dndm_update);
``jax.random`` draws broadcast over a leading batch=1 axis
(``gumbel(k, (1, N, K)) == gumbel(k, (N, K))``, same for uniform /
bernoulli, and ``categorical(k, logits) == argmax(gumbel(k,
logits.shape, logits.dtype) + logits)``) under the threefry counter
grid; and the per-row ``t/T`` normalization is the same f32 device
division the solo step performs.

Free/padded rows are parked at a sentinel time outside the schedule
(``T + 1`` on a discrete grid, ``2.0`` in continuous time); every row
step gates its update on ``live = 1 <= t <= T`` (``t <= 1.0``
continuous) so a free row passes through bit-unchanged no matter what
the shared network call computed for it.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import decode
from repro.core.posterior import posterior
from repro.core.samplers import loop
from repro.core.samplers.dndm import quantile_grid
from repro.core.samplers.dndm_topk import _reveal_topk

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True, eq=False)
class CallSchedule:
    """One request's predetermined network-call schedule.

    ``times`` is the descending sequence of diffusion times at which the
    request calls the network — for Algorithm 1/4 the unique values of
    its tau set, for the static/baseline methods the compiled grid, for
    Algorithm 2 the request's own continuous timestamps.
    ``steps_skipped`` counts the no-op grid steps the predetermined
    schedule proves it never has to pay for (T - |times|; 0 for
    continuous-time schedules, where the grid is the request itself).
    ``tau`` is None for the schedule-driven baselines (their update rule
    never consults a transition-time set).

    ``request_id`` is the serving-layer trace identity: the scheduler
    stamps the id minted at ``submit()`` onto the plan
    (``dataclasses.replace``), and every batched ``engine.stepwise``
    span lists the ids of the rows it advanced — which is what makes a
    request's full call timeline reconstructable from one trace file
    (``obs.timeline``).  ``schedule_fn`` implementations leave it None.

    Plans compare and hash by identity: a runner hands each finished
    canvas back under the plan of the request it belongs to.
    """

    times: np.ndarray                    # descending call times
    T: int                               # grid size (0 => continuous)
    tau: np.ndarray | None = None        # (N,) per-token transition times
    x0: np.ndarray | None = None         # (N,) the request's x_T draw
    step_keys: np.ndarray | None = None  # (len(times), 2) per-call keys
    request_id: str | None = None        # trace identity (scheduler-set)

    @property
    def nfe(self) -> int:
        return len(self.times)

    @property
    def steps_executed(self) -> int:
        return len(self.times)

    @property
    def steps_skipped(self) -> int:
        return max(self.T - len(self.times), 0) if self.T else 0


# ------------------------------------------------------------------
# schedule_fn per method family: (key, rt, N) -> CallSchedule
# ------------------------------------------------------------------

def dndm_plan(key: jax.Array, rt, N: int) -> CallSchedule:
    """Admission plan for the host-driven DNDM family (Alg. 1/3/4).

    Replays ``loop.setup`` for a batch of one under the request's key, so
    (tau, x_T, per-step keys) are bit-identical to what the solo sampler
    would draw — the scheduler's solo-parity guarantee starts here.
    """
    tau, x, k_loop = loop.setup(key, rt.noise, 1, N, dist=rt.dist,
                                order=rt.order, shared=rt.shared_tau)
    tau_row = np.asarray(jax.device_get(tau))[0]
    times = loop.unique_times(tau_row)
    # one split of fixed length T, cut on the host: under
    # jax_threefry_partitionable (on by default) a split's first n keys do
    # not depend on its length, so this is split(k_loop, len(times)), and
    # one program for every request whatever its call count
    step_keys = np.asarray(jax.random.split(k_loop, rt.dist.T))[:len(times)]
    return CallSchedule(times=times, T=rt.dist.T, tau=tau_row,
                        x0=np.asarray(jax.device_get(x))[0],
                        step_keys=step_keys)


def static_grid_plan(key: jax.Array, rt, N: int) -> CallSchedule:
    """dndm_static / dndm_topk_static: the (deduped) quantile grid, fixed
    NFE, the request's own tau bucketized onto it exactly as the solo
    scan does (``searchsorted`` up to the nearest grid time)."""
    from repro.core.samplers.registry import resolved_budget
    grid = np.asarray(quantile_grid(rt.dist, resolved_budget(rt, N)))
    tau, x, k_loop = loop.setup(key, rt.noise, 1, N, dist=rt.dist,
                                order=rt.order, shared=rt.shared_tau)
    tau_row = np.asarray(jax.device_get(tau))[0]
    idx = np.clip(np.searchsorted(grid, tau_row), 0, len(grid) - 1)
    step_keys = np.asarray(jax.random.split(k_loop, len(grid)))
    return CallSchedule(times=grid[::-1], T=rt.dist.T,
                        tau=grid[idx].astype(np.int32),
                        x0=np.asarray(jax.device_get(x))[0],
                        step_keys=step_keys)


def full_grid_plan(key: jax.Array, rt, N: int) -> CallSchedule:
    """Ancestral baselines (d3pm, rdm, rdm_k, mask_predict): every step.

    No transition-time set (``tau=None``) — the times are the whole grid
    — but (x_T, per-step keys) still replay the solo ``loop.setup`` /
    ``scan_loop`` streams for a batch of one.
    """
    _, x, k_loop = loop.setup(key, rt.noise, 1, N)
    times = np.arange(rt.steps, 0, -1)
    step_keys = np.asarray(jax.random.split(k_loop, len(times)))
    return CallSchedule(times=times, T=rt.steps,
                        x0=np.asarray(jax.device_get(x))[0],
                        step_keys=step_keys)


def ddim_grid_plan(key: jax.Array, rt, N: int) -> CallSchedule:
    """DDIM subsequence grid: ceil(T / stride) calls."""
    _, x, k_loop = loop.setup(key, rt.noise, 1, N)
    times = np.arange(rt.steps, 0, -rt.ddim_stride)
    step_keys = np.asarray(jax.random.split(k_loop, len(times)))
    return CallSchedule(times=times, T=rt.steps,
                        x0=np.asarray(jax.device_get(x))[0],
                        step_keys=step_keys)


def continuous_plan(key: jax.Array, rt, N: int) -> CallSchedule:
    """DNDM-C: N continuous timestamps, each its own call (NFE = N)."""
    tau, x, k_loop = loop.setup(key, rt.noise, 1, N, dist=rt.cdist,
                                order=rt.order, shared=rt.shared_tau,
                                continuous=True)
    row = np.asarray(jax.device_get(tau))[0]
    step_keys = np.asarray(jax.random.split(k_loop, N))
    return CallSchedule(times=np.sort(row)[::-1], T=0, tau=row,
                        x0=np.asarray(jax.device_get(x))[0],
                        step_keys=step_keys)


# ------------------------------------------------------------------
# batched row steps: advance every live row by one own-schedule entry
# ------------------------------------------------------------------

def _row_gumbel(keys: Array, shape, x0_mode: str) -> Array | None:
    """Per-row Gumbel slab: row b drawn from keys[b] alone, bit-identical
    to the (1, N, K) slab the solo batch-of-one step draws from that key."""
    if x0_mode == "argmax":
        return None
    with jax.named_scope("decode"):
        return jax.vmap(lambda k: jax.random.gumbel(k, shape[1:],
                                                    jnp.float32))(keys)


def _row_split(keys: Array) -> tuple[Array, Array]:
    """Per-row ``jax.random.split``: the row steps that consume two
    streams per call (rdm routing, ddim keep-mask) split each row's key
    exactly as the solo scan body splits its step key."""
    ks = jax.vmap(lambda k: jax.random.split(k))(keys)
    return ks[:, 0], ks[:, 1]


def _live(t_row: Array, T: int) -> Array:
    """Row liveness on a discrete grid: the free-row sentinel T+1 (and
    anything else outside [1, T]) must never mutate its row."""
    return (t_row >= 1) & (t_row <= T)


@partial(jax.jit, static_argnames=("denoise_fn", "noise", "cfg", "version",
                                   "T"))
def _dndm_rows(x, tau, t_row, keys, cond, *, denoise_fn, noise, cfg,
               version, T):
    """One batched network call, each row at its own time t_row[b].

    Token selection goes through ``decode_tokens`` (bitwise-identical to
    the fused kernel's argmax by the shared pre-activation contract) and
    the eq. (9) update is applied per row against its own tau set.  Rows
    whose tau has no entry at t_row[b] (including free/padded rows) pass
    through unchanged under version 1.
    """
    t_norm = t_row.astype(jnp.float32) / T
    logits = denoise_fn(x, t_norm, cond)
    g = _row_gumbel(keys, logits.shape, cfg.x0_mode)
    x0_hat, _ = decode.decode_tokens(None, logits, noise, cfg, gumbel=g)
    tcol = t_row[:, None].astype(tau.dtype)
    sel = (tau == tcol) if version == 1 else (tau >= tcol)
    sel = sel & _live(t_row, T)[:, None]
    return jnp.where(sel, x0_hat, x)


@partial(jax.jit, static_argnames=("denoise_fn", "noise", "cfg", "T"))
def _dndm_topk_rows(x, revealed, tau, t_row, keys, cond, *, denoise_fn,
                    noise, cfg, T):
    """Algorithm 4's confidence-ranked reveal, row-resumable: K_t is
    computed per row from that row's tau against that row's time."""
    t_norm = t_row.astype(jnp.float32) / T
    logits = denoise_fn(x, t_norm, cond)
    g = _row_gumbel(keys, logits.shape, cfg.x0_mode)
    x0_hat, score = decode.decode_tokens(None, logits, noise, cfg, gumbel=g)
    k_target = jnp.sum(tau >= t_row[:, None].astype(tau.dtype), axis=-1)
    k_target = jnp.where(_live(t_row, T), k_target, 0)
    return _reveal_topk(x, x0_hat, score, revealed, k_target)


@partial(jax.jit, static_argnames=("denoise_fn", "noise", "cfg", "T"))
def _d3pm_rows(x, t_row, keys, cond, alphas, *, denoise_fn, noise, cfg, T):
    """D3PM ancestral step, row-resumable: per-row (alpha_{t-1}, alpha_t)
    gather and a per-row Gumbel-max categorical draw — the same sample
    ``jax.random.categorical(step_key, log p)`` produces for a batch of
    one (categorical == argmax(gumbel + logits), and the (1, N, K)
    Gumbel slab equals the (N, K) slab under the row's key)."""
    t_norm = t_row.astype(jnp.float32) / T
    logits = denoise_fn(x, t_norm, cond) + noise.logit_mask()
    x0_probs = jax.nn.softmax(logits / cfg.temperature, axis=-1)
    # sentinel rows gather alphas[T] / clipped alphas[T+1->T]: harmless,
    # their sampled values are discarded by the live gate below
    a_tm1 = alphas[t_row - 1][:, None]
    a_t = alphas[t_row][:, None]
    p = posterior(x, x0_probs, a_tm1, a_t, noise)
    logp = jnp.log(p + 1e-30)
    g = jax.vmap(lambda k: jax.random.gumbel(k, logp.shape[1:],
                                             logp.dtype))(keys)
    x_new = jnp.argmax(logp + g, axis=-1).astype(jnp.int32)
    return jnp.where(_live(t_row, T)[:, None], x_new, x)


@partial(jax.jit, static_argnames=("denoise_fn", "noise", "cfg", "topk",
                                   "T"))
def _rdm_rows(x, denoised, t_row, keys, cond, alphas, *, denoise_fn, noise,
              cfg, topk, T):
    """RDM / RDM-k step, row-resumable: per-row clean-fraction target
    ``round(N * alpha_{t-1})`` and per-row routing noise (uniform slab
    from the row's k_route for RDM; the row's own scores for RDM-k)."""
    N = x.shape[1]
    k_sel, k_route = _row_split(keys)
    t_norm = t_row.astype(jnp.float32) / T
    logits = denoise_fn(x, t_norm, cond)
    g = _row_gumbel(k_sel, logits.shape, cfg.x0_mode)
    x0_hat, score = decode.decode_tokens(None, logits, noise, cfg, gumbel=g)
    k_target = jnp.round(N * alphas[t_row - 1]).astype(jnp.int32)
    k_target = jnp.maximum(k_target, denoised.sum(-1))  # never shrink
    if topk:
        s = jnp.where(denoised, jnp.inf, score)
    else:
        u = jax.vmap(lambda k: jax.random.uniform(k, (N,)))(k_route)
        s = jnp.where(denoised, jnp.inf, u)
    order = jnp.argsort(-s, axis=-1)
    ranks = jnp.argsort(order, axis=-1)
    in_top = ranks < k_target[..., None]
    newly = in_top & ~denoised & _live(t_row, T)[:, None]
    return jnp.where(newly, x0_hat, x), denoised | newly


@partial(jax.jit, static_argnames=("denoise_fn", "noise", "cfg", "M"))
def _mask_predict_rows(x, t_row, keys, cond, *, denoise_fn, noise, cfg, M):
    """Mask-Predict round, row-resumable.  The solo scan iterates
    ``i = 0..M-1`` with ``t_norm = (M - i) / M``; a row at grid time t
    (descending M..1) is at iteration ``i = M - t``, so the re-mask
    budget ``N * (M - 1 - i) / M`` becomes ``N * (t - 1) / M``."""
    N = x.shape[1]
    t_norm = t_row.astype(jnp.float32) / M
    logits = denoise_fn(x, t_norm, cond)
    g = _row_gumbel(keys, logits.shape, cfg.x0_mode)
    x0_hat, score = decode.decode_tokens(None, logits, noise, cfg, gumbel=g)
    n_mask = jnp.round(N * (t_row - 1) / M).astype(jnp.int32)
    order = jnp.argsort(score, axis=-1)          # ascending confidence
    ranks = jnp.argsort(order, axis=-1)
    remask = ranks < n_mask[:, None]
    x_new = jnp.where(remask, noise.mask_id, x0_hat).astype(jnp.int32)
    return jnp.where(_live(t_row, M)[:, None], x_new, x)


@partial(jax.jit, static_argnames=("denoise_fn", "noise", "cfg", "stride",
                                   "T"))
def _ddim_rows(x, t_row, keys, cond, alphas, *, denoise_fn, noise, cfg,
               stride, T):
    """Discrete-DDIM step, row-resumable: per-row sigma_t from the row's
    (t, t - stride) pair and a per-row Bernoulli keep-mask drawn from the
    row's k_jump — the stochastic per-step draw Remark 3.5 contrasts
    with DNDM's predetermined times."""
    N = x.shape[1]
    k_sel, k_jump = _row_split(keys)
    t_norm = t_row.astype(jnp.float32) / T
    logits = denoise_fn(x, t_norm, cond)
    g = _row_gumbel(k_sel, logits.shape, cfg.x0_mode)
    x0_hat, _ = decode.decode_tokens(None, logits, noise, cfg, gumbel=g)
    t_prev = jnp.maximum(t_row - stride, 0)
    a_prev, a_t = alphas[t_prev], alphas[t_row]
    sigma = (1.0 - a_prev) / jnp.maximum(1.0 - a_t, 1e-9)
    keep = jax.vmap(
        lambda k, p: jax.random.bernoulli(k, p, (N,)))(
            k_jump, jnp.clip(sigma, 0, 1))
    x_new = jnp.where(keep, x, x0_hat).astype(jnp.int32)
    return jnp.where(_live(t_row, T)[:, None], x_new, x)


@partial(jax.jit, static_argnames=("denoise_fn", "noise", "cfg", "topk"))
def _dndm_c_rows(x, revealed, tau, t_row, keys, cond, *, denoise_fn, noise,
                 cfg, topk):
    """Algorithm 2 step, row-resumable in continuous time: t_row *is* the
    row's current timestamp (passed to the denoiser raw, as the solo scan
    does).  The revealed token is the one owning the timestamp
    (``tau == t``; timestamps are a.s. distinct) or the top-score
    unrevealed one for the top-k variant.  Free rows park at the
    sentinel 2.0 > 1 and are gated out."""
    live = t_row <= 1.0
    logits = denoise_fn(x, t_row, cond)
    g = _row_gumbel(keys, logits.shape, cfg.x0_mode)
    x0_hat, score = decode.decode_tokens(None, logits, noise, cfg, gumbel=g)
    if topk:
        s = jnp.where(revealed, -jnp.inf, score)
        upd = jax.nn.one_hot(s.argmax(-1), x.shape[1], dtype=bool)
    else:
        upd = tau == t_row[:, None]
    upd = upd & live[:, None]
    return jnp.where(upd, x0_hat, x), revealed | upd


# ------------------------------------------------------------------
# stepwise_step wrappers: (state, tau, t_row, keys, cond, rt) -> state
# ------------------------------------------------------------------

def dndm_stepwise(version: int):
    """stepwise_step for dndm / dndm_static (version=1), dndm2 (2)."""
    def step(state: dict, tau, t_row, keys, cond, rt) -> dict:
        x = _dndm_rows(state["x"], tau, t_row, keys, cond,
                       denoise_fn=rt.denoise_fn, noise=rt.noise, cfg=rt.cfg,
                       version=version, T=rt.dist.T)
        return {"x": x, "revealed": state["revealed"]}
    return step


def dndm_topk_stepwise(state: dict, tau, t_row, keys, cond, rt) -> dict:
    x, revealed = _dndm_topk_rows(state["x"], state["revealed"], tau, t_row,
                                  keys, cond, denoise_fn=rt.denoise_fn,
                                  noise=rt.noise, cfg=rt.cfg, T=rt.dist.T)
    return {"x": x, "revealed": revealed}


def _alphas(rt) -> Array:
    return jnp.asarray(rt.schedule.alphas, jnp.float32)


def d3pm_stepwise(state: dict, tau, t_row, keys, cond, rt) -> dict:
    x = _d3pm_rows(state["x"], t_row, keys, cond, _alphas(rt),
                   denoise_fn=rt.denoise_fn, noise=rt.noise, cfg=rt.cfg,
                   T=rt.steps)
    return {"x": x, "revealed": state["revealed"]}


def rdm_stepwise(topk: bool):
    """stepwise_step for rdm (topk=False) / rdm_k (topk=True); the
    ``revealed`` buffer carries RDM's denoised set."""
    def step(state: dict, tau, t_row, keys, cond, rt) -> dict:
        x, denoised = _rdm_rows(state["x"], state["revealed"], t_row, keys,
                                cond, _alphas(rt), denoise_fn=rt.denoise_fn,
                                noise=rt.noise, cfg=rt.cfg, topk=topk,
                                T=rt.steps)
        return {"x": x, "revealed": denoised}
    return step


def mask_predict_stepwise(state: dict, tau, t_row, keys, cond, rt) -> dict:
    x = _mask_predict_rows(state["x"], t_row, keys, cond,
                           denoise_fn=rt.denoise_fn, noise=rt.noise,
                           cfg=rt.cfg, M=rt.steps)
    return {"x": x, "revealed": state["revealed"]}


def ddim_stepwise(state: dict, tau, t_row, keys, cond, rt) -> dict:
    x = _ddim_rows(state["x"], t_row, keys, cond, _alphas(rt),
                   denoise_fn=rt.denoise_fn, noise=rt.noise, cfg=rt.cfg,
                   stride=rt.ddim_stride, T=rt.steps)
    return {"x": x, "revealed": state["revealed"]}


def dndm_c_stepwise(topk: bool):
    """stepwise_step for dndm_c / dndm_c_topk (continuous time)."""
    def step(state: dict, tau, t_row, keys, cond, rt) -> dict:
        x, revealed = _dndm_c_rows(state["x"], state["revealed"], tau,
                                   t_row, keys, cond,
                                   denoise_fn=rt.denoise_fn, noise=rt.noise,
                                   cfg=rt.cfg, topk=topk)
        return {"x": x, "revealed": revealed}
    return step
