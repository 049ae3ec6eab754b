"""Training loop: diffusion-denoiser objective + AdamW, jit/pjit-ready.

``make_train_step`` builds the canonical train step used everywhere:
unit tests (1 device), the example drivers, and ``launch/train.py``
(jitted with NamedShardings over its mesh).  Conditional
batches carry a clean source prefix; only target positions are corrupted
and scored (the paper's MT setup with a decoder-only early-fusion twist).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import forward
from repro.core.losses import _ce
from repro.core.noise import NoiseDist
from repro.core.schedules import Schedule
from repro.models.model import Model
from repro.training import checkpoint as ckpt_lib
from repro.training.optim import AdamW

Array = jnp.ndarray


def make_train_step(model: Model, schedule: Schedule, noise: NoiseDist,
                    optimizer: AdamW, *, continuous_time: bool = False,
                    lambda_weighting: bool = True,
                    microbatches: int = 1) -> Callable:
    """Returns step(state, batch, key) -> (state, metrics).

    batch: {"x0": (B, N) int32, optional "src": (B, P) int32,
            optional "frontend_embeds": (B, F, d)}.

    ``microbatches > 1`` = gradient accumulation: the batch is split
    along dim 0 and gradients are averaged over an *unrolled* loop (the
    accumulation dependency chain keeps the live activation set to one
    microbatch — the memory-fit lever for the big MoE trains).
    """
    cfg = model.cfg

    def loss_fn(params, batch, key):
        x0 = batch["x0"]
        if continuous_time:
            x_t, t, alpha_t = forward.corrupt_continuous(
                key, x0, schedule, noise)
            t_norm = t
        else:
            x_t, t, alpha_t = forward.corrupt_for_training(
                key, x0, schedule, noise)
            t_norm = t.astype(jnp.float32) / schedule.T

        src = batch.get("src")
        inp = x_t if src is None else jnp.concatenate([src, x_t], axis=1)
        logits, aux = model.forward(
            params, inp, t_norm, batch.get("frontend_embeds"),
            causal=False)
        if src is not None:
            logits = logits[:, src.shape[1]:]

        ce = _ce(logits, x0)
        corrupted = ((x_t != x0) if noise.kind == "multinomial"
                     else (x_t == noise.mask_id))
        w = jnp.where(corrupted, 1.0, 0.05)
        if lambda_weighting:
            w = w * (1.0 - alpha_t)[:, None]
        ce_loss = (ce * w).sum() / jnp.maximum(w.sum(), 1e-6)
        loss = (ce_loss + cfg.load_balance_weight * aux["load_balance"]
                + cfg.router_z_weight * aux["router_z"])
        acc = ((logits.argmax(-1) == x0) & corrupted).sum() / jnp.maximum(
            corrupted.sum(), 1)
        return loss, {"loss": loss, "ce": ce_loss, "masked_acc": acc,
                      "load_balance": aux["load_balance"]}

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(state, batch, key):
        if microbatches > 1:
            B = batch["x0"].shape[0]
            assert B % microbatches == 0, (B, microbatches)
            mb = B // microbatches
            grads = None
            metrics = None
            for i in range(microbatches):
                sub = {k: v[i * mb:(i + 1) * mb] for k, v in batch.items()}
                (_, m_i), g_i = grad_fn(state["params"], sub,
                                        jax.random.fold_in(key, i))
                if grads is None:
                    grads, metrics = g_i, m_i
                else:
                    grads = jax.tree.map(jnp.add, grads, g_i)
                    metrics = jax.tree.map(jnp.add, metrics, m_i)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            metrics = jax.tree.map(lambda m: m / microbatches, metrics)
        else:
            (_, metrics), grads = grad_fn(state["params"], batch, key)
        params, opt, opt_metrics = optimizer.update(
            grads, state["opt"], state["params"])
        metrics.update(opt_metrics)
        return {"params": params, "opt": opt,
                "step": state["step"] + 1}, metrics

    return step


def init_state(model: Model, optimizer: AdamW, key) -> dict:
    params = model.init(key)
    return {"params": params, "opt": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32)}


@dataclasses.dataclass
class Trainer:
    """Single-host training driver with metrics + checkpointing."""

    model: Model
    schedule: Schedule
    noise: NoiseDist
    optimizer: AdamW
    continuous_time: bool = False
    log_every: int = 20
    ckpt_path: str | None = None
    ckpt_every: int = 0

    def run(self, data: Iterator[dict], steps: int, seed: int = 0,
            state: dict | None = None, verbose: bool = True) -> tuple[dict, list]:
        step_fn = jax.jit(make_train_step(
            self.model, self.schedule, self.noise, self.optimizer,
            continuous_time=self.continuous_time))
        key = jax.random.PRNGKey(seed)
        if state is None:
            key, k0 = jax.random.split(key)
            state = init_state(self.model, self.optimizer, k0)
        history = []
        t0 = time.time()
        for i, batch in enumerate(data):
            if i >= steps:
                break
            key, k = jax.random.split(key)
            batch = {kk: jnp.asarray(v) for kk, v in batch.items()}
            state, metrics = step_fn(state, batch, k)
            if i % self.log_every == 0 or i == steps - 1:
                m = {kk: float(v) for kk, v in metrics.items()}
                m["step"] = i
                m["wall"] = time.time() - t0
                history.append(m)
                if verbose:
                    print(f"step {i:5d} loss {m['loss']:.4f} "
                          f"acc {m['masked_acc']:.3f} "
                          f"lr {m['lr']:.2e} ({m['wall']:.1f}s)")
            if (self.ckpt_path and self.ckpt_every and
                    i and i % self.ckpt_every == 0):
                ckpt_lib.save(self.ckpt_path, state["params"])
        if self.ckpt_path:
            ckpt_lib.save(self.ckpt_path, state["params"])
        return state, history
