#!/usr/bin/env python3
"""Smoke test of the serving path on one TPU chip, at dndm-text8 width.

    python3 chip_smoke.py [--seed 0]

Builds the paper's text8 denoiser (12 layers, d_model 768, 12 heads,
d_ff 3072, vocab 28) with random weights from ``PRNGKey(seed)``, the way
``repro.launch.serve`` builds its engine, and serves requests on a canvas
of 256 tokens (the text8 chunk length) with the compiled Pallas decode
kernels:

1. ``BatchScheduler`` (drain mode, one AOT-compiled scan per batch) with
   ``dndm_topk_static`` (``decode_tokens``) and ``dndm_static``
   (``fused_update``);
2. ``ContinuousScheduler`` (rolling stepwise batch) with ``dndm`` and
   ``dndm_topk``, requests admitted mid-flight;
3. on one batch of the model's own logits, ``decode_tokens`` and
   ``fused_update`` must give bitwise-identical tokens under the
   ``pallas`` and ``reference`` backends;
4. solo parity of a few continuous requests, and how far one row's
   logits move between batch 8 and batch 1, printed and not gated: the
   scheduler promises solo parity only for a batch-shape-invariant
   denoiser.

Every request must come back at its length with every token in the
vocabulary and no mask id left.  The earlier lines describe this one run:
device kind, the model's widths, wall seconds per phase (compilation
included where the phase compiles) and peak device memory.  The last line
is one JSON object, ``{"ok": ..., "device": {...}}``.  Exit status: 0 when
every check passed, 1 when one failed, 2 without any result when JAX finds
no TPU or ``REPRO_DECODE_BACKEND`` is set.  Runs in one process.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

ARCH = "dndm-text8"
SEQ = 256               # text8 chunk length (Hoogeboom et al. 2021)
MAX_BATCH = 8
STEPS = 50              # T of the linear schedule
NFE_BUDGET = 16         # quantile grid of the static samplers
REQUESTS = 16           # per method and scheduler
SOLO_CHECKS = 3
BATCH_METHODS = ("dndm_topk_static", "dndm_static")
CONTINUOUS_METHODS = ("dndm", "dndm_topk")


def say(msg: str) -> None:
    print(msg, flush=True)


def check_results(done: dict, want: int, vocab: int,
                  mask_id: int) -> list[str]:
    """Every request completed at its length, each token a vocabulary id
    other than the mask."""
    errs = [] if len(done) == want else [
        f"{len(done)} of {want} requests completed"]
    for r in done.values():
        toks = np.asarray(r.result)
        if toks.shape != (r.length,):
            errs.append(f"{r.request_id}: shape {toks.shape}, "
                        f"want ({r.length},)")
        elif ((toks < 0) | (toks >= vocab)).any():
            errs.append(f"{r.request_id}: token outside [0, {vocab})")
        elif (toks == mask_id).any():
            errs.append(f"{r.request_id}: "
                        f"{int((toks == mask_id).sum())} mask ids left")
    return errs


def batch_phase(engine, method: str, lengths, seed: int) -> list[str]:
    from repro.serving import BatchScheduler
    sched = BatchScheduler(engine, max_batch=MAX_BATCH, bucket_len=SEQ,
                           seed=seed)
    for n in lengths:
        sched.submit(int(n), method=method)
    t0 = time.perf_counter()
    done = sched.run()
    wall = time.perf_counter() - t0
    steady = sorted({r.batch_wall for r in done.values()})
    say(f"batch {method}: {len(done)} requests in {wall:.3f} s "
        f"(AOT compile included), NFE per batch "
        f"{next(iter(done.values())).nfe}, steady batch wall "
        f"{', '.join(f'{w:.4f}' for w in steady)} s")
    return check_results(done, len(lengths), engine.model.cfg.vocab_size,
                         engine.noise.mask_id)


def continuous_phase(engine, lengths, seed: int):
    from repro.serving import ContinuousScheduler
    sched = ContinuousScheduler(engine, max_batch=MAX_BATCH, bucket_len=SEQ,
                                seed=seed)
    for n in lengths:
        for m in CONTINUOUS_METHODS:
            sched.submit(int(n), method=m)
    t0 = time.perf_counter()
    first = None
    while sched.pump():
        if first is None:
            first = time.perf_counter() - t0
    wall = time.perf_counter() - t0
    done = sched.done
    nfe = sum(r.nfe for r in done.values())
    say(f"continuous {'+'.join(CONTINUOUS_METHODS)}: {len(done)} requests "
        f"in {wall:.3f} s ({sched.total_calls} batched calls, first call "
        f"{first:.3f} s with its compile), per-request NFE sum {nfe}")
    errs = check_results(done, len(lengths) * len(CONTINUOUS_METHODS),
                         engine.model.cfg.vocab_size, engine.noise.mask_id)
    return errs, done


def decode_parity(engine, seed: int) -> list[str]:
    """Kernel vs reference tokens on one batch of real denoiser logits."""
    import jax
    import jax.numpy as jnp

    from repro.core import decode
    from repro.core.samplers import SamplerConfig
    noise, c = engine.noise, engine.cfg
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 4)
    # a half-revealed canvas, as the denoiser sees mid-trajectory
    clean = jax.random.randint(ks[0], (MAX_BATCH, SEQ), 0, noise.mask_id)
    x = jnp.where(jax.random.bernoulli(ks[1], 0.5, (MAX_BATCH, SEQ)),
                  clean, noise.mask_id).astype(jnp.int32)
    denoise = jax.jit(engine.denoise_fn)
    t_norm = jnp.full((MAX_BATCH,), 0.5, jnp.float32)
    logits = denoise(x, t_norm, engine.call_cond(None))
    # reported, not gated: how far the denoiser is from batch-shape
    # invariance, which solo parity of continuous requests rests on
    solo = denoise(x[:1], t_norm[:1], engine.call_cond(None))
    say(f"logits of row 0 in batch {MAX_BATCH} vs alone: max |diff| "
        f"{float(jnp.max(jnp.abs(logits[:1] - solo))):.3g}")
    tau = jax.random.randint(ks[2], (MAX_BATCH, SEQ), 1, STEPS + 1)
    t = STEPS // 2
    errs = []
    for mode in ("argmax", "sample"):
        cfg = SamplerConfig(x0_mode=mode, temperature=c.temperature)
        dec = {b: jax.jit(lambda k, lg, b=b: decode.decode_tokens(
            k, lg, noise, cfg, backend=b))(ks[3], logits)
            for b in ("pallas", "reference")}
        (tk, sk), (tr, sr) = dec["pallas"], dec["reference"]
        same = bool(jnp.array_equal(tk, tr))
        dscore = float(jnp.max(jnp.abs(sk - sr)))
        say(f"parity decode_tokens {mode}: tokens bitwise equal {same}, "
            f"max |score diff| {dscore:.3g}")
        if not same:
            errs.append(f"decode_tokens {mode}: "
                        f"{int((tk != tr).sum())} tokens differ")
        for version in (1, 2):
            upd = {b: jax.jit(lambda k, lg, b=b: decode.fused_update(
                k, lg, x, tau, t, noise, cfg, version=version, backend=b))(
                    ks[3], logits)
                for b in ("pallas", "reference")}
            same = bool(jnp.array_equal(upd["pallas"], upd["reference"]))
            # the two ops pick the same token from the same key
            sel = (tau == t) if version == 1 else (tau >= t)
            agree = bool(jnp.array_equal(jnp.where(sel, tk, x),
                                         upd["pallas"]))
            say(f"parity fused_update {mode} v{version}: tokens bitwise "
                f"equal {same}, agrees with decode_tokens {agree}")
            if not same:
                errs.append(f"fused_update {mode} v{version}: tokens differ")
            if not agree:
                errs.append(f"fused_update {mode} v{version} disagrees "
                            "with decode_tokens")
    return errs


def solo_parity(engine, done: dict) -> None:
    """Replay a few continuous requests solo from their keys; reported,
    not gated."""
    for r in sorted(done.values(), key=lambda r: r.rid)[:SOLO_CHECKS]:
        solo, _ = engine.generate(r.key, 1, SEQ, method=r.method)
        got = np.asarray(solo.tokens)[0][: r.length]
        diff = int((got != r.result).sum())
        say(f"solo parity {r.request_id} {r.method} len {r.length}: "
            f"{'identical' if diff == 0 else f'{diff} tokens differ'}")


def run(engine, *, seed: int = 0) -> bool:
    """All phases on a built engine; True when every gated check passed."""
    rng = np.random.default_rng(seed)
    failures: list[str] = []
    done: dict = {}

    def phase(name, fn, *args):
        t0 = time.perf_counter()
        try:
            out = fn(*args)
        except Exception as e:  # noqa: BLE001 — reported, run goes on
            traceback.print_exc()
            failures.append(f"{name}: {type(e).__name__}: {e}")
            return None
        finally:
            say(f"phase {name}: {time.perf_counter() - t0:.3f} s wall")
        return out

    for m in BATCH_METHODS:
        lengths = rng.integers(SEQ // 2, SEQ + 1, size=REQUESTS)
        failures += phase(f"batch/{m}", batch_phase, engine, m, lengths,
                          seed) or []
    lengths = rng.integers(SEQ // 2, SEQ + 1, size=REQUESTS)
    out = phase("continuous", continuous_phase, engine, lengths, seed)
    if out is not None:
        failures += out[0]
        done = out[1]
    failures += phase("decode_parity", decode_parity, engine, seed) or []
    if done:
        phase("solo_parity", solo_parity, engine, done)
    for f in failures:
        say(f"FAILED {f}")
    return not failures


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if os.environ.get("REPRO_DECODE_BACKEND"):
        print("chip_smoke: REPRO_DECODE_BACKEND is set; the smoke test "
              "runs only the decode backend JAX picks for the chip",
              file=sys.stderr)
        return 2

    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              "this test runs only on a TPU", file=sys.stderr)
        return 2
    from repro.core import decode
    from repro.launch.serve import build_engine

    say(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"compile cache {cache_dir}")
    backend = decode.resolve_backend()
    say(f"decode backend: {backend}")
    t0 = time.perf_counter()
    engine = build_engine(ARCH, seed=args.seed, method=CONTINUOUS_METHODS[0],
                          steps=STEPS, nfe_budget=NFE_BUDGET,
                          shared_tau=False)
    mc = engine.model.cfg
    n_params = engine.model.param_count(engine.params)
    say(f"model {mc.name}: n_layers {mc.n_layers}, d_model {mc.d_model}, "
        f"n_heads {mc.n_heads}, d_ff {mc.d_ff}, vocab {mc.vocab_size}, "
        f"dtype {mc.dtype}, {n_params} params, canvas {SEQ}, "
        f"max_batch {MAX_BATCH} (built in {time.perf_counter() - t0:.3f} s)")
    ok = backend == "pallas"
    if not ok:
        say(f"FAILED decode backend is {backend!r}, not 'pallas'")
    ok = run(engine, seed=args.seed) and ok
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        say(f"peak_bytes_in_use: {stats['peak_bytes_in_use']}")
    print(json.dumps({"ok": ok, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
