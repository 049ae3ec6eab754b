"""Distributed training launcher.

On real hardware this builds the production mesh, shards params/optimizer
with the rule system and runs the pjit train step; on a CPU it runs the
same code path over however many devices exist (use launch/dryrun.py for
the 512-device compile-only validation).

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --reduced --steps 20 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.configs as configs_lib
from repro.core import noise as noise_lib, schedules as sched_lib
from repro.data import DataConfig, DataPipeline
from repro.launch.sharding import ShardingPolicy, shard_params_tree, tokens_spec
from repro.models.model import Model
from repro.training import checkpoint
from repro.training.optim import AdamW, warmup_cosine
from repro.training.trainer import init_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dndm-text8")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--T", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--data-axis", type=int, default=0,
                    help="data-parallel size (0 = all devices)")
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args()

    n_dev = len(jax.devices())
    dp = args.data_axis or n_dev
    mesh = jax.make_mesh((dp, n_dev // dp), ("data", "model"))
    policy = ShardingPolicy()

    cfg = configs_lib.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = cfg.replace(bidirectional=True)
    model = Model(cfg)
    sch = sched_lib.linear(args.T)
    nz = noise_lib.absorbing(cfg.vocab_size)
    opt = AdamW(schedule=warmup_cosine(args.lr, 20, args.steps))

    key = jax.random.PRNGKey(0)
    state = init_state(model, opt, key)
    # shard the live state across the mesh
    state = {
        "params": shard_params_tree(state["params"], mesh, policy, cfg),
        "opt": {"mu": shard_params_tree(state["opt"]["mu"], mesh, policy,
                                        cfg),
                "nu": shard_params_tree(state["opt"]["nu"], mesh, policy,
                                        cfg),
                "step": state["opt"]["step"]},
        "step": state["step"],
    }
    step_fn = jax.jit(make_train_step(model, sch, nz, opt))

    pipe = DataPipeline(DataConfig(task="unconditional",
                                   vocab=cfg.vocab_size - 1,
                                   seq_len=args.seq, batch=args.batch))
    tok_sharding = NamedSharding(mesh, tokens_spec(mesh, args.batch,
                                                   policy))
    t0 = time.time()
    for i, batch in enumerate(pipe):
        if i >= args.steps:
            break
        key, k = jax.random.split(key)
        x0 = jax.device_put(jnp.asarray(batch["x0"]), tok_sharding)
        state, metrics = step_fn(state, {"x0": x0}, k)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss {float(metrics['loss']):.4f} "
                  f"acc {float(metrics['masked_acc']):.3f} "
                  f"({time.time() - t0:.1f}s)", flush=True)
    if args.ckpt:
        checkpoint.save(args.ckpt, state["params"])
        print(f"saved params -> {args.ckpt}")


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
