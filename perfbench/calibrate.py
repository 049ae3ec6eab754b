#!/usr/bin/env python3
"""Readings that set the benchmark's fixed numbers, made on the chip.

    python3 perfbench/calibrate.py readings --workload <cell> \
        --seeds 101-112 --seconds 10
    python3 perfbench/calibrate.py sweep --workload <cell> \
        --rates 6,8,10,12 --seconds 20 --seed 7

``readings`` runs the cell once per seed in one process, as a benchmark
run does, and prints per seed the program's compared numbers and verdict,
and the control's on the same requests (the configuration's
``check.control`` mode of the reference put in the program's place,
judged by the same checks), with both gap numbers of each (``gaps``)
whether compared or not.  The largest program reading of a number over
the seeds and the smallest control reading are the two readings its
limit (``check.<number>_limit``) lies between.

``sweep`` serves the cell's open-loop traffic at each given rate (and,
with ``--backlog``, from a full queue first, which gives the rate the
system completes at saturation) and prints the latency percentiles and
the completed rate: the knee is the highest rate whose latency does not
grow with the window.

The benchmark's own runs run neither.  One JSON line per run on stdout.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[1])]

from perfbench import run  # noqa: E402  (puts src/ on the path)
from perfbench import cell as cell_lib  # noqa: E402


def _seeds(spec: str) -> list[int]:
    out = []
    for part in spec.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b) + 1)) if b else [int(a)]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("readings", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default="")
    ap.add_argument("--backlog", action="store_true")
    args = ap.parse_args(argv)
    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    peaks = run.peaks.for_kind(dev.device_kind)
    cell = cell_lib.load(args.workload)
    if args.what == "readings":
        for seed in _seeds(args.seeds):
            res = run.run_cell(cell, seed, args.seconds, False, peaks,
                               control=True)
            print(json.dumps({"seed": seed, "correct": res["correct"],
                              "checks": res["checks"], "gaps": res["gaps"],
                              "control": res["control"],
                              "metrics": res["metrics"]}), flush=True)
        return 0
    e2e = tuple({"name": n, "unit": u} for n, u in (
        ("tokens_per_s", "tokens/s"), ("latency_p50_s", "s"),
        ("latency_p95_s", "s"), ("setup_s", "s")))
    variants = []
    if args.backlog:
        variants.append(("backlog", dict(cell.traffic, arrivals="backlog")))
    for r in filter(None, args.rates.split(",")):
        variants.append((float(r), dict(cell.traffic, rate_per_s=float(r))))
    for rate, traffic in variants:
        c = dataclasses.replace(cell, traffic=traffic, end_to_end=e2e
                                if traffic["arrivals"] == "poisson"
                                else e2e[:1] + e2e[3:])
        res = run.run_cell(c, args.seed, args.seconds, False, peaks)
        done_rate = (res["metrics"]["tokens_per_s"]["value"]
                     / ((traffic["length_min"] + traffic["length_max"]) / 2))
        print(json.dumps({"rate": rate, "completed_per_s": done_rate,
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "correct": res["correct"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
