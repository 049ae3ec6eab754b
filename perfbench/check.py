"""The output check that decides ``correct``.

These numbers are compared, each against its own limit:

* ``bad_results`` (limit 0): requests of the run that never completed,
  or came back at another length than asked, with an id outside the
  vocabulary, or with a mask id left;
* ``window_compiles`` (limit 0): programs compiled or loaded while the
  window (and the drain after it) ran;
* ``logit_gap`` and ``mean_logit_gap``, each where the configuration file
  states its limit (``check.<name>_limit``): the served tokens against
  the plain reference.  A DNDM request reveals position j once, at the
  call whose time equals its transition time tau_j, and never changes it
  again, so the canvas each call saw is known from the served tokens
  alone: ``where(tau > t, served, mask)``.  The reference runs over those
  canvases, and for each position revealed inside the request's length it
  adds the same Gumbel noise the program drew from that call's key.  The
  gap is how far the served token's perturbed reference logit lies below
  the best one; ``logit_gap`` is the widest gap over the sampled requests
  and ``mean_logit_gap`` the mean over every position checked (0 where
  the served token is the reference's pick).  The widest gap swings from
  seed to seed by its nature; the mean is steadier, and separates a
  control whose rounding noise is only a few times the program's.  The
  reference computes at the precision the configuration states
  (``check.precision``, a mode of the reference module).  The control
  reads, at the same canvases and noise, the gap of the token that the
  reference at the next precision down (``check.control``) puts first.
"""
from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np

MASK_PENALTY = -1e9         # the absorbing [MASK] never wins a selection


@dataclasses.dataclass
class Served:
    """What the check needs of one served request."""
    length: int
    canvas: np.ndarray          # (N,) final tokens of the whole canvas
    tau: np.ndarray             # (N,) transition times
    times: np.ndarray           # (nfe,) call times, descending
    step_keys: np.ndarray       # (nfe, 2) uint32 per-call keys


def bad_result(tokens, length: int, vocab: int, mask_id: int) -> bool:
    t = np.asarray(tokens)
    return (t.shape != (length,) or bool(((t < 0) | (t >= vocab)).any())
            or bool((t == mask_id).any()))


def reference(conf: dict):
    return importlib.import_module(f"perfbench.refs.{conf['reference']}")


def sample(completed: list, k: int, seed: int) -> list:
    """``k`` requests drawn from the seed, the longest always among them."""
    if not completed:
        return []
    longest = max(range(len(completed)), key=lambda i: completed[i].length)
    rest = [i for i in range(len(completed)) if i != longest]
    rng = np.random.default_rng([int(seed) % 2**64, 7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [completed[longest]] + [completed[rest[i]] for i in sorted(pick)]


def _pad(a: np.ndarray, n: int) -> np.ndarray:
    return np.concatenate([a, np.repeat(a[-1:], n - len(a), axis=0)])


@jax.jit
def _gaps(logits_ref, logits_ctrl, g, served, valid, pen):
    """(widest, summed) gap of the served tokens over the valid positions,
    and of the control's picks (``None`` without a control)."""
    a = logits_ref + pen + g
    best = a.max(-1)
    got = jnp.take_along_axis(a, served[:, None], -1)[:, 0]
    gap = jnp.where(valid, best - got, 0.0)
    if logits_ctrl is None:
        return (gap.max(), gap.sum()), None
    pick = jnp.argmax(logits_ctrl + pen + g, -1)
    alt = jnp.take_along_axis(a, pick[:, None], -1)[:, 0]
    ctrl = jnp.where(valid, best - alt, 0.0)
    return (gap.max(), gap.sum()), (ctrl.max(), ctrl.sum())


@jax.jit
def _noise_rows(keys, sel_c, sel_j, vocab_row):
    """Gumbel rows (P, V): row p is position sel_j[p] of the (N, V) slab
    that call sel_c[p] drew from its key, as the program draws it."""
    n = sel_j.shape[0]
    slabs = jax.vmap(lambda k: jax.random.gumbel(
        k, (n, vocab_row.shape[0]), jnp.float32))(keys)
    return slabs[sel_c, sel_j]


def replay_gap(params, conf: dict, reqs: list[Served], *, steps: int,
               block: int, precision: str,
               control: str | None = None) -> dict:
    """The gaps of the served tokens over ``reqs`` against the reference
    in mode ``precision``: the widest (``logit_gap``) and the mean over
    every checked position (``mean_logit_gap``); and the same of the
    control's picks under ``"control"`` when ``control`` names a
    reference mode."""
    ref = reference(conf)
    vocab, mask_id = conf["vocab_size"], conf["mask_id"]
    pen = jnp.zeros((vocab,), jnp.float32).at[mask_id].set(MASK_PENALTY)
    vrow = jnp.zeros((vocab,), jnp.float32)
    acc = [0, 0.0, 0.0, 0.0, 0.0]   # tokens, widest, sum (and control)
    for r in reqs:
        n = len(r.canvas)
        nfe = len(r.times)
        call_of = {int(t): c for c, t in enumerate(r.times)}
        pos_call = np.array([call_of[int(t)] for t in r.tau])
        states = np.where(r.tau[None, :] > r.times[:, None],
                          r.canvas[None, :], mask_id).astype(np.int32)
        t_norm = (r.times.astype(np.float32) / np.float32(steps))
        for c0 in range(0, nfe, block):
            c1 = min(c0 + block, nfe)
            j = np.nonzero((pos_call >= c0) & (pos_call < c1)
                           & (np.arange(n) < r.length))[0]
            if not len(j):
                continue
            acc[0] += len(j)
            sel_c = np.zeros(n, np.int32)
            sel_j = np.zeros(n, np.int32)
            valid = np.zeros(n, bool)
            sel_c[:len(j)] = pos_call[j] - c0
            sel_j[:len(j)] = j
            valid[:len(j)] = True
            served = np.zeros(n, np.int32)
            served[:len(j)] = r.canvas[j]
            x = jnp.asarray(_pad(states[c0:c1], block))
            t = jnp.asarray(_pad(t_norm[c0:c1], block))
            keys = jnp.asarray(_pad(r.step_keys[c0:c1], block), jnp.uint32)
            g = _noise_rows(keys, jnp.asarray(sel_c), jnp.asarray(sel_j),
                            vrow)
            h = ref.hidden(params, x, t, conf, mode=precision)
            lg = ref.logits(params, h[sel_c, sel_j], mode=precision)
            lc = None
            if control:
                hc = ref.hidden(params, x, t, conf, mode=control)
                lc = ref.logits(params, hc[sel_c, sel_j], mode=control)
            a, b = _gaps(lg, lc, g, jnp.asarray(served), jnp.asarray(valid),
                         pen)
            for k, got in ((1, a), (3, b)):
                if got is not None:
                    acc[k] = max(acc[k], float(got[0]))
                    acc[k + 1] += float(got[1])
    tokens = acc[0]

    def stats(k):
        return {"logit_gap": acc[k],
                "mean_logit_gap": acc[k + 1] / tokens if tokens else 0.0}
    out = dict(stats(1), tokens=tokens)
    if control:
        out["control"] = stats(3)
    return out
