#!/usr/bin/env python3
"""Run one cell traced and report what the program's spans and scopes say.

    python3 perfbench/layers.py --workload <cell> --seed <n> \
        [--record <file.json>] [--save <file.json.gz>]

The run is ``run.py --trace 1``'s (same set-up, traced window and
output check), and the trace's program spans and named scopes
(``progtrace``) are read before the trace is deleted.  The last line of
standard output is the run's result object with a ``program`` key:

* ``metrics``: the readers under ``metrics/`` of :data:`METRICS`;
* ``idle_ms_per_step``: the window's device-idle time per step
  execution, by the innermost span open, program or ``bench.*`` (``""``
  where none is);
* ``pump_idle_share``: of the device-idle time inside ``bench.pump``,
  the share under each innermost program span;
* ``scoped_share``: the scope metrics' sum over ``step_device_ms``, and
  ``unscoped_ops``: the step's operations under no scope, by time;
* ``spans_per_step`` and ``span_cost_us``: one ``obs.span`` on this
  host with no profiler session and with one recording.

``--record`` writes two steps of the trace's raw events and the metrics
read from them (the fixture of ``tests/test_perfbench_progtrace.py``);
``--save`` writes every event of the window the reductions look at, with
all its stats.  Needs a TPU, as ``run.py`` does; exits 2 without one.
"""
from __future__ import annotations

import argparse
import gzip
import json
import pathlib
import shutil
import sys
import tempfile
import time
import types

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from perfbench import run  # noqa: E402  (first: its clock starts set-up)
from perfbench import cell as cell_lib  # noqa: E402
from perfbench import devtrace, peaks, progtrace  # noqa: E402

import jax  # noqa: E402

METRICS = ("attention_device_ms", "mlp_device_ms", "lm_head_device_ms",
           "decode_device_ms", "idle_ms.submit", "idle_ms.admit",
           "idle_ms.dispatch", "idle_ms.harvest", "padded_position_share")
SCOPE_METRICS = METRICS[:4]
ENGINE_SPANS = ("engine.admit", "engine.stepwise", "engine.harvest")


def span_cost_us(n: int = 20_000) -> dict:
    """Microseconds of one ``engine.stepwise``-like span, telemetry off,
    with no profiler session and with one recording."""
    from repro import obs

    def per_span() -> float:
        t0 = time.perf_counter()
        for i in range(n):
            with obs.span("engine.stepwise", method="dndm", call=i, rows=8,
                          padded_positions=512):
                pass
        return (time.perf_counter() - t0) / n * 1e6
    out = {"off": per_span()}
    d = tempfile.mkdtemp(prefix="perfbench-spancost-")
    jax.profiler.start_trace(d)
    try:
        out["on"] = per_span()
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(d, ignore_errors=True)
    return out


def read_all(trace, prog, cell) -> dict:
    ctx = types.SimpleNamespace(trace=trace, program=prog, devtrace=devtrace,
                                traffic=cell.traffic, conf=cell.config)
    return {m: run.read_metric(m, ctx) for m in METRICS}


def unscoped_ops(dev_raw, prog_raw, trace, top: int = 8) -> list[list]:
    """The step program's operations that carry no scope, by total
    device seconds in the window."""
    prog = devtrace.main_program(trace)
    runs = devtrace.executions(trace, prog) if prog else []
    scoped = {(s, e) for p, _, _, s, e, _ in prog_raw
              if p.startswith("/device:")}
    lo, hi = trace.window
    tot: dict[str, float] = {}
    for plane, line, name, s, e in dev_raw:
        if (line != "XLA Ops" or (s, e) in scoped or not lo <= s < hi
                or not any(a <= s < b for a, b in runs)):
            continue
        op = devtrace.op_name(name)
        if op.split(".")[0] not in devtrace.CONTAINERS:
            tot[op] = tot.get(op, 0.0) + (e - s) * 1e-9
    return [[n, t] for n, t in sorted(tot.items(), key=lambda kv: -kv[1])
            ][:top]


def all_events(path: str) -> list[list]:
    """Every event the two reductions look at, with all its stats (strings
    cut to 300 characters) and, for a device operation, its scope path:
    the device's operations and program executions, and the host's
    program and ``bench.*`` spans."""
    from jax.profiler import ProfileData
    scopes = progtrace.op_scopes(path)
    out = []
    for plane in ProfileData.from_file(progtrace.trace_file(path)).planes:
        device = plane.name.startswith("/device:TPU:")
        if not (device or plane.name.startswith("/host:")):
            continue
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for e in line.events:
                if not (device or e.name in progtrace.SPANS
                        or e.name.startswith(devtrace.HOST_PREFIX)):
                    continue
                stats = {k: v if isinstance(v, (int, float)) else str(v)[:300]
                         for k, v in e.stats}
                if e.name in scopes:
                    stats[progtrace.SCOPE_STAT] = scopes[e.name]
                out.append([plane.name, line.name, e.name[:300], e.start_ns,
                            e.end_ns, stats])
    return out


def record(events, trace, cell) -> dict:
    """The step execution before the device idles for the window's last
    harvest (the harvest's copy, the admission and the next dispatch
    that follow it), up to the end of that dispatch, as one list of raw
    events (device operations keep only their scope stat), and the
    metrics read from it."""
    runs = devtrace.executions(trace, devtrace.main_program(trace) or "")
    ends = sorted((e for _, _, n, _, e, _ in events if n == "engine.harvest"),
                  reverse=True)
    for end in ends:
        gap = [k for k in range(len(runs) - 1)
               if runs[k][1] <= end <= runs[k + 1][0]]
        if gap:
            i = gap[0]
            break
    else:
        raise ValueError("no harvest between two step executions to record")
    lo, hi = runs[i][0], max([runs[i + 1][0]] + [
        e for _, _, n, s, e, _ in events
        if n == "engine.stepwise" and runs[i][1] <= s < runs[i + 1][1]])
    raw = [["/host:CPU", "python3", devtrace.WINDOW, 0, hi - lo, {}]]
    for plane, line, name, s, e, stats in events:
        if e <= lo or s >= hi or name == devtrace.WINDOW:
            continue
        if plane.startswith("/device:"):
            name = name[:40]
            stats = {k: v for k, v in stats.items()
                     if k == progtrace.SCOPE_STAT}
        raw.append([plane, line, name, s - lo, e - lo, stats])
    raw = [r[:3] + [int(r[3]), int(r[4])] + r[5:] for r in raw]
    sub_trace = devtrace.reduce([r[:5] for r in raw
                                 if r[0].startswith("/device:")
                                 or r[2].startswith(devtrace.HOST_PREFIX)])
    return {"about": f"one step execution and the idle gap of the last "
                     f"harvest after it, of a traced {cell.name} run (cut by "
                     "perfbench/layers.py record()); device op names cut to "
                     "40 characters, times in ns from the execution's start",
            "raw": raw,
            "expected": read_all(sub_trace, progtrace.reduce(raw), cell)}


def breakdown(tr, prog_raw, dev_raw, cell) -> dict:
    """What the program's spans and scopes say about a traced window."""
    prog = progtrace.reduce(prog_raw)
    out = {"metrics": read_all(tr, prog, cell),
           "span_cost_us": span_cost_us()}
    steps = len(devtrace.executions(tr, devtrace.main_program(tr) or ""))
    if not steps:
        return out
    step_ms = run.read_metric("step_device_ms", types.SimpleNamespace(
        trace=tr, devtrace=devtrace))
    bench = [(n, s, e, {}) for n, s, e in tr.host if n != devtrace.WINDOW]
    every = progtrace.ProgramTrace(spans=prog.spans + bench, scoped=[],
                                   window=prog.window)
    by_span = progtrace.innermost_idle_ns(tr, every)
    pumps = [(s, e) for n, s, e in tr.host if n == "bench.pump"]
    in_pump = progtrace.innermost_idle_ns(tr, prog, within=pumps)
    pump_total = sum(in_pump.values()) or 1.0
    lo, hi = prog.window
    out.update({
        "step_device_ms": step_ms,
        "steps": steps,
        "idle_ms_per_step": {n: 1e-6 * v / steps
                             for n, v in sorted(by_span.items())},
        "pump_idle_share": {n: v / pump_total
                            for n, v in sorted(in_pump.items())},
        "pump_idle_under_engine": sum(in_pump.get(n, 0.0)
                                      for n in ENGINE_SPANS) / pump_total,
        "scoped_share": sum(out["metrics"][m] or 0.0
                            for m in SCOPE_METRICS) / step_ms,
        "time_embed_device_ms": progtrace.scope_ms_per_step(
            tr, prog, "time_embed"),
        "unscoped_ops": unscoped_ops(dev_raw, prog_raw, tr),
        "spans_per_step": sum(lo <= s < hi for _, s, _, _ in prog.spans)
        / steps,
    })
    return out


def measure(cell, seed: int, device_peaks: dict, record_path=None,
            save_path=None) -> dict:
    """``run.run_cell`` traced, with the program's breakdown added as
    ``result["program"]``."""
    kept: dict = {}

    class KeepingTracer(run.Tracer):
        """``run.Tracer`` that keeps the raw events as well, before its
        ``read`` deletes the trace."""

        def read(self):
            kept["prog_raw"] = progtrace.raw_events(self.dir)
            kept["dev_raw"] = devtrace.raw_events(self.dir)
            if record_path or save_path:
                kept["events"] = all_events(self.dir)
            kept["trace"], program = super().read()
            return kept["trace"], program

    run.Tracer = KeepingTracer
    result = run.run_cell(cell, seed, run.TRACE_S, True, device_peaks,
                          t_start=run.T_START)
    tr, prog_raw, dev_raw = kept["trace"], kept["prog_raw"], kept["dev_raw"]
    result["program"] = breakdown(tr, prog_raw, dev_raw, cell)
    if record_path:
        pathlib.Path(record_path).write_text(json.dumps(
            record(kept["events"], tr, cell), separators=(",", ":")))
    if save_path:
        lo, hi = tr.window
        with gzip.open(save_path, "wt") as f:
            json.dump([ev for ev in kept["events"]
                       if ev[4] > lo and ev[3] < hi], f)
    checks = result.pop("checks")
    result["checks"] = checks           # the compared numbers come last
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--record", help="write a trimmed raw recording here")
    ap.add_argument("--save", help="write the window's events here "
                    "(.json.gz)")
    args = ap.parse_args(argv)
    cell = cell_lib.load(args.workload)
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        run.say(f"layers: needs a TPU; JAX found {dev.platform}")
        return 2
    result = measure(cell, args.seed, peaks.for_kind(dev.device_kind),
                     args.record, args.save)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
