"""Reduce a JAX profiler trace to what the per-layer metrics read.

A trace (``*.xplane.pb`` under ``<dir>/plugins/profile/<run>/``) holds one
plane per TPU and one for the host.  The reduction keeps three kinds of
intervals, all on the profiler's own clock in nanoseconds:

* device operations: events of a device plane's ``XLA Ops`` line, named
  by their HLO instruction (``fusion.12``, ``decode_scores.1``; the trace
  gives the whole instruction text);
* device programs: events of its ``XLA Modules`` line (one per execution
  of a compiled program, named after the jitted function);
* host spans: the host plane's events whose names start with ``bench.``,
  written by the harness with ``jax.profiler.TraceAnnotation``.

The ``bench.window`` span bounds the traced window; everything is clipped
to it.  :func:`raw_events` and :func:`reduce` are separate steps, so a
small recorded list of raw events can be kept with the tests.
"""
from __future__ import annotations

import dataclasses
import glob
import os

HOST_PREFIX = "bench."
WINDOW = "bench.window"
WAIT = "bench.wait"

Interval = tuple[str, float, float]     # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    ops: list[Interval]         # device operations, all chips together
    modules: list[Interval]     # device program executions
    host: list[Interval]        # bench.* host spans
    window: tuple[float, float]
    chips: int = 1
    # executions of each program that ended before the window opened
    modules_before: dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def _clip(events: list[Interval], lo: float, hi: float) -> list[Interval]:
    out = []
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    return out


def raw_events(path: str) -> list[tuple[str, str, str, float, float]]:
    """(plane, line, name, start_ns, end_ns) of the events the reduction
    reads, from the trace under ``path`` (a directory or an .xplane.pb)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise FileNotFoundError(f"want one .xplane.pb under {path}, "
                                    f"found {len(files)}")
        path = files[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:TPU:")
        if not (device or plane.name.startswith("/host:")):
            continue
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            out.extend((plane.name, line.name, e.name, e.start_ns, e.end_ns)
                       for e in line.events
                       if device or e.name.startswith(HOST_PREFIX))
    return out


def reduce(raw) -> Trace:
    """The :class:`Trace` of raw events, clipped to ``bench.window``."""
    ops: list[Interval] = []
    modules: list[Interval] = []
    host: list[Interval] = []
    chips = set()
    for plane, line, name, s, e in raw:
        if plane.startswith("/device:TPU:"):
            chips.add(plane)
            if line == "XLA Ops":
                ops.append((op_name(name), s, e))
            else:
                modules.append((name, s, e))
        else:
            host.append((name, s, e))
    wins = [(s, e) for n, s, e in host if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW} span in the trace")
    lo, hi = wins[0]
    before: dict[str, int] = {}
    for name, s, e in modules:
        if e <= lo:
            before[name] = before.get(name, 0) + 1
    return Trace(ops=_clip(ops, lo, hi), modules=_clip(modules, lo, hi),
                 host=_clip(host, lo, hi), window=(lo, hi),
                 chips=max(len(chips), 1), modules_before=before)


def from_xplane(path: str) -> Trace:
    return reduce(raw_events(path))


def union_ns(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran on the device, averaged over
    the chips traced."""
    return union_ns((s, e) for _, s, e in trace.ops) * 1e-9 / trace.chips


def idle_gaps(trace: Trace) -> list[tuple[float, float]]:
    """Intervals of the window in which no device operation ran."""
    gaps, cur = [], trace.window[0]
    for s, e in sorted((s, e) for _, s, e in trace.ops):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if trace.window[1] > cur:
        gaps.append((cur, trace.window[1]))
    return gaps


def _overlap(a: tuple[float, float], b: tuple[float, float]) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def attribute_gaps(trace: Trace, top: int = 10) -> list[list]:
    """The longest idle gaps, each named by the host span that covers
    most of it (innermost first: a ``bench.submit`` inside a pump wins
    over the pump), as [[name, seconds], ...]."""
    spans = sorted(((n, s, e) for n, s, e in trace.host if n != WINDOW),
                   key=lambda x: x[2] - x[1])
    out = []
    for g in sorted(idle_gaps(trace), key=lambda g: g[0] - g[1])[:top]:
        best, cover = "host (no bench span)", 0.0
        for n, s, e in spans:
            c = _overlap(g, (s, e))
            if c > cover * 1.5:
                best, cover = n, c
        out.append([best, (g[1] - g[0]) * 1e-9])
    return out


CONTAINERS = ("while", "conditional", "call")


def top_ops(trace: Trace, top: int = 10) -> list[list]:
    """Device operations that took most time, by name: [[name, s], ...].
    Control-flow ops (a ``while`` over the layers) span the operations
    they run and are left out."""
    tot: dict[str, float] = {}
    for n, s, e in trace.ops:
        if n.split(".")[0] in CONTAINERS:
            continue
        tot[n] = tot.get(n, 0.0) + (e - s)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[n, t * 1e-9 / trace.chips] for n, t in ranked]


def main_program(trace: Trace) -> str | None:
    """The device program that took most time in the window: in a
    serving cell, the batched denoiser step."""
    tot: dict[str, float] = {}
    for n, s, e in trace.modules:
        tot[n] = tot.get(n, 0.0) + (e - s)
    return max(tot, key=tot.get) if tot else None


def executions(trace: Trace, program: str) -> list[tuple[float, float]]:
    return sorted((s, e) for n, s, e in trace.modules if n == program)


def step_gaps_ns(trace: Trace, program: str) -> list[float]:
    """Gaps between consecutive executions of ``program``, leaving out
    any gap in which the harness waited for arrivals (no row was live)."""
    runs = executions(trace, program)
    waits = [(s, e) for n, s, e in trace.host if n == WAIT]
    gaps = []
    for (_, e0), (s1, _) in zip(runs, runs[1:]):
        if any(_overlap((e0, s1), w) > 0 for w in waits):
            continue
        gaps.append(max(0.0, s1 - e0))
    return gaps


def op_time_ns(trace: Trace, kernel: str) -> tuple[float, int]:
    """(total ns, count) of the device operations named ``kernel`` or
    ``kernel.<n>``."""
    hits = [e - s for n, s, e in trace.ops
            if n == kernel or n.rsplit(".", 1)[0] == kernel]
    return sum(hits), len(hits)


def mean_step_gap_ms(trace: Trace) -> float | None:
    """Mean gap between consecutive executions of the main program
    (:func:`main_program`) while a row is live, in milliseconds."""
    prog = main_program(trace)
    gaps = step_gaps_ns(trace, prog) if prog else []
    return 1e-6 * sum(gaps) / len(gaps) if gaps else None
