"""Benchmark harness — one function per paper table.

Prints ``name,us_per_call,derived`` CSV rows.  Set REPRO_BENCH_QUICK=0
for the full (slow) grids; default quick mode finishes on a laptop CPU.
``--json PATH`` switches to the per-method perf-baseline emitter
(wall / compile / NFE / tokens-per-second + telemetry snapshot, see
benchmarks/baseline.py; schema validated by ``repro.obs.schema``).
Set ``REPRO_TRACE=trace.jsonl`` to additionally export the span/event
trace (per-step |R_t|, jit-cache, backend selection) as JSON lines.
A table module that raises is reported on stdout and makes the run exit
with status 1 once the remaining modules have run.

  bench_nfe           -> Tables 7/8  (avg NFE vs T, Theorem D.1)
  bench_speed         -> Fig. 1/4    (wall-clock scaling in steps)
  bench_quality       -> Tables 2/3  (BLEU + time, conditional MT)
  bench_unconditional -> Table 4     (unconditional text, ppl proxy)
  bench_schedules     -> Table 5     (transition-time schedule ablation)
  bench_order         -> Table 6     (l2r / r2l transition order)
  bench_beta_grid     -> Tables 9/10 (Beta(a,b) grid)
  bench_continuous    -> Tables 11/12 (continuous train/sample)
  bench_maskpredict   -> Table 13    (Mask-Predict comparison)
  roofline            -> EXPERIMENTS §Roofline (from dry-run artifacts)
"""
from __future__ import annotations

import os
import sys
import time
import traceback

QUICK = os.environ.get("REPRO_BENCH_QUICK", "1") == "1"


def _out_path(argv: list, flag: str) -> str:
    i = argv.index(flag)
    try:
        path = argv[i + 1]
    except IndexError:
        path = ""
    if not path or path.startswith("--"):
        raise SystemExit(f"{flag} needs an output path, e.g. "
                         f"{flag} BENCH{flag[1:].replace('-', '_')}.json "
                         "(quick mode is REPRO_BENCH_QUICK=1, not a flag)")
    return path

MODULES = [
    "bench_nfe", "bench_speed", "bench_quality", "bench_unconditional",
    "bench_schedules", "bench_order", "bench_beta_grid",
    "bench_continuous", "bench_maskpredict", "bench_static_budget",
    "bench_ddim",
    "roofline",
]


def main() -> None:
    argv = sys.argv[1:]
    if "--json" in argv:
        # perf-baseline mode: per-method wall/NFE/tokens-per-second JSON
        # (see benchmarks/baseline.py) instead of the CSV table sweep
        path = _out_path(argv, "--json")
        from benchmarks.baseline import emit
        emit(path, quick=QUICK)
        return
    if "--serving-live" in argv:
        # live-observability leg: the Poisson serving benchmark with the
        # HTTP exporter up, /metrics scraped+validated mid-run, and the
        # live p95 checked against the artifact sketch (obs_live.py);
        # CI gates the artifact with `python -m repro.obs.regress`
        path = _out_path(argv, "--serving-live")
        from benchmarks.obs_live import run_live
        run_live(path, quick=QUICK)
        return
    if "--serving-registry" in argv:
        # full-registry serving leg: every registered method through the
        # drain and continuous schedulers (see benchmarks/serving.py)
        path = _out_path(argv, "--serving-registry")
        from benchmarks.serving import emit_registry
        emit_registry(path, quick=QUICK)
        return
    if "--serving" in argv:
        # Poisson-arrival serving benchmark: drain vs continuous batching
        # (see benchmarks/serving.py; "kind": "serving" schema-2 JSON)
        path = _out_path(argv, "--serving")
        from benchmarks.serving import emit
        emit(path, quick=QUICK)
        return
    only = argv or MODULES
    from benchmarks.common import available_methods
    # stderr: stdout stays a machine-readable CSV stream
    print(f"# engine methods: {', '.join(available_methods())}",
          file=sys.stderr)
    print("name,us_per_call,derived")
    failed = []
    for name in MODULES:
        if name not in only:
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            rows = mod.run(quick=QUICK)
            for r in rows:
                print(r, flush=True)
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
