"""Reduce the program's own spans and named scopes from a profiler trace.

:mod:`devtrace` keeps the harness's ``bench.*`` spans and the device's
operations by HLO name.  This module keeps, from the same trace file,
what the program itself writes there:

* program spans: host events named like the serving path's
  ``repro.obs`` spans (``scheduler.submit`` and the others in
  :data:`SPANS`), each with its stats (``rows``, ``padded_positions``,
  ...).  ``obs.span`` writes them whenever a profiler session records;
* scoped operations: device operations whose named-scope path (the
  :data:`SCOPE_STAT` stat of the operation's event metadata, e.g.
  ``jit(_dndm_rows)/while/body/closed_call/mlp/dot_general:``) names one
  of the denoiser's scopes (:data:`SCOPES`), charged to the innermost.
  ``jax.profiler.ProfileData`` gives an event's own stats but not its
  metadata's, so :func:`op_scopes` reads those from the trace file's
  protobuf encoding (``XSpace``, tsl/profiler/protobuf/xplane.proto).

Times are the profiler's nanoseconds, the clock devtrace's intervals are
on.  Spans overlapping ``bench.window`` are kept whole (a reader decides
which count); scoped operations are clipped to the window.
:func:`raw_events` and :func:`reduce` are separate steps, so a small
recorded list of raw events can be kept with the tests.
"""
from __future__ import annotations

import dataclasses
import glob
import os

from perfbench import devtrace

SPANS = ("scheduler.submit", "engine.plan", "scheduler.pump",
         "engine.admit", "engine.stepwise", "engine.harvest")
SCOPES = ("time_embed", "attention", "mlp", "lm_head", "decode")
SCOPE_STAT = "tf_op"

Span = tuple[str, float, float, dict]       # (name, start_ns, end_ns, stats)


@dataclasses.dataclass
class ProgramTrace:
    spans: list[Span]                           # program spans, unclipped
    scoped: list[tuple[str, float, float]]      # (scope, start, end) ops
    window: tuple[float, float]


def scope_of(path: str) -> str | None:
    """The innermost of :data:`SCOPES` in a scope path, if any."""
    for part in reversed(path.rstrip(":").split("/")):
        if part in SCOPES:
            return part
    return None


def trace_file(path: str) -> str:
    """The one ``.xplane.pb`` under ``path``, or ``path`` itself."""
    if os.path.isdir(path):
        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise FileNotFoundError(f"want one .xplane.pb under {path}, "
                                    f"found {len(files)}")
        return files[0]
    return path


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field, None for a fixed one."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} in a trace file")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode()


def op_scopes(path: str) -> dict[str, str]:
    """The :data:`SCOPE_STAT` of each device operation, by the name its
    events carry, from the event metadata of the trace's TPU planes.
    XPlane fields: 2 name, 4 event_metadata (map entry: 2 value),
    5 stat_metadata (map entry: 2 value); XEventMetadata: 2 name,
    5 stats; XStatMetadata: 1 id, 2 name; XStat: 1 metadata_id,
    5 str_value, 7 ref_value (a stat_metadata id whose name is the
    string)."""
    with open(trace_file(path), "rb") as f:
        space = memoryview(f.read())
    out: dict[str, str] = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, metas, stat_names = "", [], {}
        for field, v in _fields(plane):
            if field == 2:
                name = _text(v)
            elif field == 4:
                metas.append(v)
            elif field == 5:
                sm = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[sm.get(1, 0)] = _text(sm.get(2, b""))
        wanted = {i for i, n in stat_names.items() if n == SCOPE_STAT}
        if not name.startswith("/device:TPU:") or not wanted:
            continue
        for entry in metas:
            op, scope = "", None
            for field, v in _fields(dict(_fields(entry)).get(2, b"")):
                if field == 2:
                    op = _text(v)
                elif field == 5:
                    st = dict(_fields(v))
                    if st.get(1) in wanted:
                        scope = (_text(st[5]) if 5 in st
                                 else stat_names.get(st.get(7)))
            if scope:
                out[op] = scope
    return out


def raw_events(path: str) -> list[tuple[str, str, str, float, float, dict]]:
    """(plane, line, name, start_ns, end_ns, stats) of the events the
    reduction reads, from the trace under ``path``: the host's program
    spans and ``bench.window``, and the device operations that carry a
    scope (stats cut to :data:`SCOPE_STAT`)."""
    from jax.profiler import ProfileData
    scopes = op_scopes(path)
    out = []
    for plane in ProfileData.from_file(trace_file(path)).planes:
        device = plane.name.startswith("/device:TPU:")
        if not (device or plane.name.startswith("/host:")):
            continue
        for line in plane.lines:
            if device and line.name != "XLA Ops":
                continue
            for e in line.events:
                if device:
                    scope = scopes.get(e.name, "")
                    if scope_of(scope):
                        out.append((plane.name, line.name, e.name,
                                    e.start_ns, e.end_ns,
                                    {SCOPE_STAT: scope}))
                elif e.name in SPANS or e.name == devtrace.WINDOW:
                    out.append((plane.name, line.name, e.name, e.start_ns,
                                e.end_ns, dict(e.stats)))
    return out


def reduce(raw) -> ProgramTrace:
    """The :class:`ProgramTrace` of raw events, bounded by
    ``bench.window``."""
    wins = [(s, e) for _, _, n, s, e, _ in raw if n == devtrace.WINDOW]
    if not wins:
        raise ValueError(f"no {devtrace.WINDOW} span in the trace")
    lo, hi = wins[0]
    spans, scoped = [], []
    for plane, _, name, s, e, stats in raw:
        if plane.startswith("/device:"):
            scope = scope_of(str(stats.get(SCOPE_STAT, "")))
            s, e = max(s, lo), min(e, hi)
            if scope and e > s:
                scoped.append((scope, s, e))
        elif name in SPANS and e > lo and s < hi:
            spans.append((name, s, e, stats))
    spans.sort(key=lambda sp: sp[1])
    return ProgramTrace(spans=spans, scoped=scoped, window=(lo, hi))


def from_xplane(path: str) -> ProgramTrace:
    return reduce(raw_events(path))


# ---------------------------------------------------------------- readers

def _step_runs(trace) -> list[tuple[float, float]]:
    prog = devtrace.main_program(trace)
    return devtrace.executions(trace, prog) if prog else []


def scope_ms_per_step(trace, prog: ProgramTrace, scope: str) -> float | None:
    """Device milliseconds of the operations charged to ``scope`` that
    start inside an execution of the step program, per execution in the
    window (the step program and its executions as ``step_device_ms``
    counts them)."""
    runs = _step_runs(trace)
    if not runs:
        return None
    ops = [(s, e) for sc, s, e in prog.scoped if sc == scope
           and any(a <= s < b for a, b in runs)]
    if not ops:
        return None
    return 1e-6 * devtrace.union_ns(ops) / len(runs)


def _intersect(xs, ys) -> list[tuple]:
    """Overlaps of two sorted lists of disjoint intervals; each overlap
    keeps what follows the bounds in ``ys`` (a name)."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b, *ys[j][2:]))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def innermost_segments(spans: list[Span]) -> list[tuple[float, float, str]]:
    """The times some program span is open, cut where the innermost open
    span changes and named by it.  Spans nest on the one serving thread,
    so the innermost is the one opened last that is still open."""
    edges = sorted([(s, 1, i) for i, (_, s, _, _) in enumerate(spans)]
                   + [(e, 0, i) for i, (_, _, e, _) in enumerate(spans)])
    segs, stack, prev = [], [], 0.0
    for t, opens, i in edges:
        if stack and t > prev:
            segs.append((prev, t, spans[stack[-1]][0]))
        if opens:
            stack.append(i)
        else:
            stack.remove(i)
        prev = t
    return segs


def innermost_idle_ns(trace, prog: ProgramTrace,
                      within: list[tuple[float, float]] | None = None
                      ) -> dict[str, float]:
    """Device-idle nanoseconds of the window by the innermost program span
    open at the time (``""`` where none is), restricted to the disjoint
    intervals ``within`` if given."""
    idle = devtrace.idle_gaps(trace)
    if within is not None:
        idle = _intersect(idle, sorted(within))
    out = {"": sum(b - a for a, b, *_ in idle)}
    for a, b, name in _intersect(idle, innermost_segments(prog.spans)):
        out[name] = out.get(name, 0.0) + (b - a)
        out[""] -= b - a
    return out


def idle_ms_per_step(trace, prog: ProgramTrace,
                     names: tuple[str, ...]) -> float | None:
    """Device-idle milliseconds during which the innermost open program
    span is one of ``names``, per execution of the step program in the
    window."""
    runs = _step_runs(trace)
    if not runs:
        return None
    idle = innermost_idle_ns(trace, prog)
    return 1e-6 * sum(idle.get(n, 0.0) for n in names) / len(runs)


def padded_position_share(prog: ProgramTrace, canvas: int) -> float | None:
    """Padded positions over computed positions, in percent, over the
    ``engine.stepwise`` dispatches that began in the window: the sum of
    their ``padded_positions`` over the sum of ``rows`` x ``canvas``."""
    lo, hi = prog.window
    steps = [st for n, s, _, st in prog.spans
             if n == "engine.stepwise" and lo <= s < hi
             and "rows" in st and "padded_positions" in st]
    computed = sum(st["rows"] for st in steps) * canvas
    if not computed:
        return None
    return 100.0 * sum(st["padded_positions"] for st in steps) / computed
