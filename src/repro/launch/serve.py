"""Serving launcher: load a checkpoint (or init), build the generation
engine, drain a batch of synthetic requests.

    PYTHONPATH=src python -m repro.launch.serve --arch dndm-text8 \
        --reduced --requests 16 --method dndm_topk_static
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

import repro.configs as configs_lib
from repro.core.samplers import registry
from repro.models.model import Model
from repro.serving import BatchScheduler, EngineConfig, GenerationEngine
from repro.training import checkpoint


def build_engine(arch: str = "dndm-text8", *, reduced: bool = False,
                 ckpt: str = "", seed: int = 0,
                 **engine_kw) -> GenerationEngine:
    """The serving engine for ``arch``: params from ``ckpt``, or random
    from ``PRNGKey(seed)``; ``engine_kw`` fills :class:`EngineConfig`."""
    cfg = configs_lib.get(arch)
    if reduced:
        cfg = cfg.reduced()
    cfg = cfg.replace(bidirectional=True)
    model = Model(cfg)
    if ckpt:
        params = jax.tree.map(jnp.asarray, checkpoint.load(ckpt))
    else:
        params = model.init(jax.random.PRNGKey(seed))
    return GenerationEngine(model, params, EngineConfig(**engine_kw))


def main():
    ap = argparse.ArgumentParser(
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog="registered samplers:\n" + registry.describe())
    ap.add_argument("--arch", default="dndm-text8")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--method", default="dndm_topk_static",
                    choices=registry.names(),
                    help="sampler (from the registry)")
    ap.add_argument("--noise-kind", default="absorbing",
                    choices=("absorbing", "multinomial"))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--nfe-budget", type=int, default=16)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--len", type=int, default=64)
    args = ap.parse_args()

    engine = build_engine(args.arch, reduced=args.reduced, ckpt=args.ckpt,
                          method=args.method, steps=args.steps,
                          nfe_budget=args.nfe_budget,
                          noise_kind=args.noise_kind)
    sched = BatchScheduler(engine, max_batch=args.max_batch,
                           bucket_len=args.len)
    t0 = time.time()
    for _ in range(args.requests):
        sched.submit(args.len)
    done = sched.run()
    wall = time.time() - t0
    nfe = sum(r.nfe for r in done.values())
    print(f"{len(done)} requests in {wall:.2f}s "
          f"({len(done) / wall:.2f} req/s), total NFE {nfe}")


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
