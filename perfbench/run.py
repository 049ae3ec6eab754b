#!/usr/bin/env python3
"""Run one benchmark cell on the TPU this process finds.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell's denoiser with weights made from ``--seed`` on the
device, warms the programs the cell's traffic uses, brings the rolling
batch to a steady state, and then measures ``--seconds`` of serving
through ``ContinuousScheduler``.  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` serves the same window, profiles its
first seconds and reports their per-layer metrics.  After the window the
served tokens are checked against the plain reference (``check.py``).

The last line of standard output is one JSON object; the last lines of
standard error give each compared number beside its limit.  With no TPU,
fewer chips than the cell asks for, or a device kind missing from the
peaks table, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from perfbench import cell as cell_lib  # noqa: E402
from perfbench import check, devtrace, flops, peaks, progtrace  # noqa: E402
from perfbench import tape as tape_lib  # noqa: E402
from perfbench import weights  # noqa: E402
from perfbench.drive import LoadGen  # noqa: E402

METRICS_DIR = pathlib.Path(__file__).resolve().parent / "metrics"
TRACE_S = 4.0       # a --trace 1 run's per-layer metrics read only the
                    # first seconds of its window, which it profiles
GAPS = ("logit_gap", "mean_logit_gap")      # check.py's numbers of the
                                            # served tokens, each compared
                                            # where its limit is stated
_COMPILES: list[float] = []     # perf_counter stamps of program lowerings


def _on_event(name: str, *_a, **_k) -> None:
    if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
        _COMPILES.append(time.perf_counter())


jax.monitoring.register_event_duration_secs_listener(_on_event)


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def nearest_rank(values, q: float) -> float:
    """The q-th percentile by nearest rank: the smallest value with at
    least q% of the sample at or below it."""
    v = sorted(values)
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


def nfe_law(steps: int, n: int) -> tuple[float, float]:
    """Mean and standard deviation of a request's call count: the number
    of distinct values among ``n`` transition times uniform on 1..steps
    (the linear schedule's law)."""
    q1, q2 = (1 - 1 / steps) ** n, (1 - 2 / steps) ** n
    mean = steps * (1 - q1)
    var = steps * q1 + steps * (steps - 1) * q2 - steps ** 2 * q1 ** 2
    return mean, math.sqrt(max(var, 0.0))


def warm(engine, traffic: dict) -> None:
    """Compile (or load) every program the cell's traffic runs: the plan
    path at (1, canvas) on the host's CPU device (one fixed-length key
    split whatever a request's call count), the admission scatter for
    1..max_batch rows at once, and the batched step at (max_batch,
    canvas)."""
    n, rows, method = traffic["canvas"], traffic["max_batch"], \
        traffic["method"]
    plan = engine.plan_request(jax.random.PRNGKey(0), n, method)
    for k in range(1, rows + 1):
        runner = engine.stepwise(rows, n, method)
        runner.admit_many([(row, plan) for row in range(k)])
    runner.step()
    jax.block_until_ready(runner.x)


class Tracer:
    """Profiles the run into a directory under ``TMPDIR``, read and
    deleted after the run.  The profiler starts during set-up, since
    starting it takes seconds, and stops when the first ``seconds`` of
    the window have passed (stopping it stalls the host while it writes
    the trace; the run serves on untraced); the ``bench.window`` span
    marks the seconds the metrics read."""

    def __init__(self, load: LoadGen, seconds: float):
        self.load = load
        self.seconds = seconds
        self.dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # it slows the host twofold
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.state = "idle"
        self.window = jax.profiler.TraceAnnotation("bench.window")

    def __call__(self, now: float) -> None:
        d = self.load
        if self.state == "idle" and now >= d.t_open:
            self.window.__enter__()
            self.state = "on"
        elif self.state == "on" and now >= self.t_close:
            self.stop()

    @property
    def t_close(self) -> float:
        return min(self.load.t_open + self.seconds, self.load.t_close)

    def stop(self) -> None:
        if self.state == "on":
            self.window.__exit__(None, None, None)
        if self.state != "done":
            jax.profiler.stop_trace()
            self.state = "done"

    def read(self) -> tuple[devtrace.Trace, progtrace.ProgramTrace]:
        """The harness's reduction of the trace and the program's own
        spans and scopes in it; the trace is deleted after."""
        try:
            t0 = time.perf_counter()
            trace = devtrace.from_xplane(self.dir)
            t1 = time.perf_counter()
            program = progtrace.from_xplane(self.dir)
            say(f"trace read: devtrace {t1 - t0:.3f} s, program events "
                f"{time.perf_counter() - t1:.3f} s ({len(program.spans)} "
                f"spans, {len(program.scoped)} scoped operations)")
            return trace, program
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def read_metric(name: str, ctx) -> float | None:
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name}", METRICS_DIR / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def build(conf: dict, traffic: dict, seed: int):
    from repro.models.model import Model
    from repro.serving import EngineConfig, GenerationEngine
    model = Model(cell_lib.model_config(conf))
    params = weights.make(model, seed, conf["num_hidden_layers"])
    jax.block_until_ready(params)
    engine = GenerationEngine(model, params, EngineConfig(
        method=traffic["method"], steps=traffic["steps"],
        schedule=traffic["schedule"], x0_mode=traffic["x0_mode"],
        shared_tau=traffic["shared_tau"], temperature=1.0))
    return params, engine


def run_cell(cell: cell_lib.Cell, seed: int, seconds: float, trace: bool,
             device_peaks: dict, t_start: float | None = None,
             control: bool = False) -> dict:
    """Run one cell on the devices JAX has; returns the result object.
    ``control`` also puts the configuration's control (see ``check.py``)
    in the program's place on the same requests and judges it by the same
    checks, as ``result["control"]``."""
    from repro.serving import ContinuousScheduler
    conf, traffic = cell.config, cell.traffic
    if not any(f"{name}_limit" in conf["check"] for name in GAPS):
        raise ValueError(f"{conf['name']}: the check states a limit for "
                         f"none of {GAPS}")
    t_start = time.perf_counter() if t_start is None else t_start
    params, engine = build(conf, traffic, seed)
    warm(engine, traffic)
    tape = tape_lib.make(traffic, seed, seconds)
    sched = ContinuousScheduler(engine, max_batch=traffic["max_batch"],
                                bucket_len=traffic["canvas"], seed=seed)
    load = LoadGen(sched, engine, tape, max_batch=traffic["max_batch"],
                   nfe_mean=nfe_law(traffic["steps"], traffic["canvas"])[0])
    tracer = Tracer(load, TRACE_S) if trace else None
    load.trace_hook = tracer
    if tape.open_loop:
        load.run_open_loop(seconds)
    else:
        load.run_backlog(seconds)
    if tracer:
        tracer.stop()
    t_end = time.perf_counter()
    setup_s = load.t_open - t_start
    compiles = sum(t >= load.t_open for t in _COMPILES)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))

    # ---- what the window saw ----
    window_s = load.t_close - load.t_open
    done = load.completed_in_window()
    vocab, mask_id = conf["vocab_size"], conf["mask_id"]
    if tape.open_loop:
        attempted = load.scheduled_in_window()
        deadline = load.t_close + 60.0
        lat = [(r.done if r.done is not None else deadline) - r.scheduled
               for r in attempted]
        late = [r.submitted - r.scheduled for r in attempted]
        say(f"generator: {len(attempted)} arrivals in the window, submit "
            f"late by mean {np.mean(late) * 1e3:.3f} ms, max "
            f"{np.max(late) * 1e3:.3f} ms")
    else:
        attempted, lat = done, []
    failed = sum(r.done is None or bad_result(r, vocab, mask_id)
                 for r in attempted)
    bad = sum(r.done is not None and bad_result(r, vocab, mask_id)
              for r in load.recs.values())
    bad += sum(r.done is None for r in attempted)
    say(f"window {window_s:.3f} s: {len(done)} requests completed, "
        f"{load.next} submitted in the run, setup {setup_s:.3f} s, "
        f"run {t_end - t_start:.3f} s, peak_bytes_in_use {mem_peak}")

    # the readers of a traced run see its traced seconds; the check below
    # samples the whole window's completions either way
    seen, seen_done, seen_sched = window_s, done, attempted
    if trace:
        t_seen = tracer.t_close
        seen = t_seen - load.t_open
        seen_done = [r for r in done if r.done < t_seen]
        seen_sched = [r for r in attempted if r.scheduled < t_seen]
    ctx = types.SimpleNamespace(
        cell=cell, conf=conf, traffic=traffic, peaks=device_peaks,
        flops=flops, devtrace=devtrace, window_s=seen, completed=seen_done,
        scheduled=seen_sched, latencies=lat, nearest_rank=nearest_rank,
        clock_offset=time.time() - time.perf_counter(),
        live_rows=load.live_rows, trace=None, program=None)
    result: dict = {"correct": False, "attempted": len(attempted),
                    "failed": int(failed), "metrics": {}}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    if trace:
        tr, ctx.program = tracer.read()
        ctx.trace = tr
        device["busy_s"] = devtrace.busy_s(tr)
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": devtrace.top_ops(tr),
                               "idle_gaps": devtrace.attribute_gaps(tr)}
        wanted = cell.per_layer
    else:
        wanted = cell.end_to_end
    for m in wanted:
        if m["name"] == "setup_s":
            value = setup_s
        elif m["name"] in END_TO_END:
            value = END_TO_END[m["name"]](ctx)
        else:
            value = read_metric(m["name"], ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    result["device"] = device

    # ---- the check, once the program's state is freed ----
    spec = conf["check"]
    sampled = check.sample(done, spec["requests"], seed)
    served = [served_of(r) for r in sampled]
    del load, sched, engine, tracer, ctx, done, attempted
    gc.collect()
    t0 = time.perf_counter()
    if any(s is None for s in served) or not served:
        none = {name: 1e9 for name in GAPS}
        gap = dict(none, control=none, tokens=0)
    else:
        gap = check.replay_gap(
            params, conf, served, steps=traffic["steps"],
            block=spec["block"], precision=spec["precision"],
            control=spec["control"] if control else None)
    say(f"reference: {len(served)} requests, {gap['tokens']} tokens "
        f"checked in {time.perf_counter() - t0:.3f} s")

    def judged(gaps: dict) -> tuple[bool, dict]:
        checks = {
            "bad_results": {"value": int(bad), "limit": 0},
            "window_compiles": {"value": int(compiles), "limit": 0},
        }
        for name in GAPS:
            if f"{name}_limit" in spec:
                checks[name] = {"value": gaps[name],
                                "limit": spec[f"{name}_limit"]}
        return all(c["value"] <= c["limit"] for c in checks.values()), checks

    if control:
        ok, checks = judged(gap["control"])
        result["control"] = {"mode": spec["control"], "correct": ok,
                             "checks": checks, "gaps": gap["control"]}
        result["gaps"] = {name: gap[name] for name in GAPS}
    result["correct"], result["checks"] = judged(gap)
    for name, c in result["checks"].items():
        say(f"check {name} {c['value']} limit {c['limit']}")
    return result


def bad_result(rec, vocab: int, mask_id: int) -> bool:
    return check.bad_result(rec.req.result, rec.length, vocab, mask_id)


def served_of(rec) -> check.Served | None:
    if rec.canvas is None:
        return None
    plan = rec.req.plan
    return check.Served(length=rec.length,
                        canvas=np.asarray(rec.canvas, np.int32),
                        tau=np.asarray(plan.tau, np.int64),
                        times=np.asarray(plan.times, np.int64),
                        step_keys=np.asarray(plan.step_keys, np.uint32))


def _tokens_per_s(ctx) -> float:
    return sum(r.length for r in ctx.completed) / ctx.window_s


def _latency(q: float):
    def read(ctx):
        return ctx.nearest_rank(ctx.latencies, q) if ctx.latencies else None
    return read


END_TO_END = {
    "tokens_per_s": _tokens_per_s,
    "latency_p50_s": _latency(50),
    "latency_p95_s": _latency(95),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = cell_lib.load(args.workload)
    except (OSError, KeyError, ValueError) as e:
        say(f"perfbench: cannot load workload {args.workload!r}: {e}")
        return 2
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < cell.chips:
        say(f"perfbench: cell {cell.name} needs {cell.chips} TPU chip(s); "
            f"JAX found {len(devices)} {dev.platform} device(s)")
        return 2
    try:
        device_peaks = peaks.for_kind(dev.device_kind)
    except KeyError as e:
        say(f"perfbench: {e}")
        return 2
    say(f"perfbench: {cell.name} seed {args.seed} on {dev.device_kind} "
        f"x{len(devices)}, compile cache {cache_dir}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device_peaks, t_start=T_START)
    checks = result.pop("checks")
    result["checks"] = checks           # the compared numbers come last
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
