"""The reduction of the program's spans and scopes, and the readers of
the metrics built on it, on a hand-made trace whose answers are known
and on a recorded phi3-backlog trace."""
import json
import pathlib
import types

import pytest

from perfbench import devtrace, progtrace, run
from perfbench.tests.test_perfbench_trace import MS, hand_trace

DATA = pathlib.Path(__file__).with_name("data")
SCOPE_READERS = ("attention_device_ms", "mlp_device_ms",
                 "lm_head_device_ms", "decode_device_ms")


def hand_program() -> progtrace.ProgramTrace:
    # on test_perfbench_trace's hand trace (step program at 10-20, 30-40
    # and 70-80 ms; idle 0-10, 20-30, 40-45, 47-70, 80-100): the serving
    # thread's spans, and the step's operations by scope
    step = {"method": "dndm"}
    spans = [
        ("scheduler.pump", 5 * MS, 21 * MS, {}),
        ("engine.admit", 5 * MS, 8 * MS, {"rows": 2}),
        ("engine.stepwise", 8 * MS, 10 * MS,
         dict(step, rows=2, padded_positions=100)),
        ("engine.harvest", 18 * MS, 21 * MS, {"rows": 1}),
        ("scheduler.pump", 21 * MS, 41 * MS, {}),
        ("engine.stepwise", 21 * MS, 29 * MS,
         dict(step, rows=2, padded_positions=100)),
        ("scheduler.submit", 41 * MS, 45 * MS, {"length": 200}),
        ("engine.plan", 42 * MS, 44 * MS, {}),
        ("scheduler.pump", 68 * MS, 81 * MS, {}),
        ("engine.admit", 68 * MS, 69 * MS, {"rows": 1}),
        ("engine.stepwise", 69 * MS, 71 * MS,
         dict(step, rows=1, padded_positions=0)),
    ]
    scoped = [("mlp", 10 * MS, 15 * MS), ("decode", 14 * MS, 20 * MS),
              ("mlp", 30 * MS, 35 * MS), ("decode", 35 * MS, 40 * MS),
              ("attention", 45 * MS, 47 * MS),    # not in a step: left out
              ("mlp", 70 * MS, 76 * MS), ("decode", 76 * MS, 80 * MS)]
    return progtrace.ProgramTrace(spans=spans, scoped=scoped,
                                  window=(0.0, 100 * MS))


def _ctx(trace, program, canvas=256):
    return types.SimpleNamespace(trace=trace, program=program,
                                 devtrace=devtrace,
                                 traffic={"canvas": canvas})


def test_scope_of_takes_the_innermost_scope():
    assert progtrace.scope_of(
        "jit(_dndm_rows)/while/body/closed_call/mlp/dot_general") == "mlp"
    assert progtrace.scope_of("jit(f)/attention/decode/add") == "decode"
    assert progtrace.scope_of("jit(f)/attentions/x") is None
    assert progtrace.scope_of("") is None


def test_innermost_segments_follow_nesting():
    spans = [("a", 0, 10, {}), ("b", 2, 4, {}), ("c", 4, 6, {}),
             ("d", 12, 13, {})]
    assert progtrace.innermost_segments(spans) == [
        (0, 2, "a"), (2, 4, "b"), (4, 6, "c"), (6, 10, "a"), (12, 13, "d")]


def test_idle_by_innermost_span():
    idle = progtrace.innermost_idle_ns(hand_trace(), hand_program())
    assert {k: v / MS for k, v in idle.items()} == pytest.approx({
        "": 5 + 21 + 19, "engine.admit": 3 + 1,
        "engine.stepwise": 2 + 8 + 1, "engine.harvest": 1,
        "scheduler.pump": 1 + 1 + 1, "scheduler.submit": 2,
        "engine.plan": 2})
    # inside the harness's pumps alone
    inside = progtrace.innermost_idle_ns(
        hand_trace(), hand_program(),
        within=[(s, e) for n, s, e in hand_trace().host
                if n == "bench.pump"])
    assert {k: v / MS for k, v in inside.items() if v} == pytest.approx({
        "engine.stepwise": 2 + 8 + 1, "engine.harvest": 1,
        "scheduler.pump": 1 + 1 + 1, "engine.admit": 3 + 1})


def test_metric_readers_on_hand_program_trace():
    ctx = _ctx(hand_trace(), hand_program())
    read = lambda name: run.read_metric(name, ctx)  # noqa: E731
    # three executions of the step program in the window
    assert read("mlp_device_ms") == pytest.approx(16 / 3)
    assert read("decode_device_ms") == pytest.approx(15 / 3)
    assert read("attention_device_ms") is None
    assert read("lm_head_device_ms") is None
    assert read("idle_ms.submit") == pytest.approx(4 / 3)
    assert read("idle_ms.admit") == pytest.approx(4 / 3)
    assert read("idle_ms.dispatch") == pytest.approx(11 / 3)
    assert read("idle_ms.harvest") == pytest.approx(1 / 3)
    assert read("padded_position_share") == pytest.approx(
        100 * 200 / (5 * 256))


@pytest.mark.parametrize("name", SCOPE_READERS + (
    "idle_ms.submit", "idle_ms.admit", "idle_ms.dispatch",
    "idle_ms.harvest", "padded_position_share"))
def test_readers_are_silent_without_program_events(name):
    """A run whose program writes no spans or scopes (or a run.py that
    passes none) reads nothing, and does not raise."""
    ctx = types.SimpleNamespace(trace=hand_trace(), devtrace=devtrace,
                                traffic={"canvas": 256})
    assert run.read_metric(name, ctx) is None
    empty = progtrace.ProgramTrace(spans=[], scoped=[],
                                   window=(0.0, 100 * MS))
    value = run.read_metric(name, _ctx(hand_trace(), empty))
    assert value is None or value == 0.0


def test_reduce_clips_ops_and_keeps_spans_whole():
    raw = [("/host:CPU", "python3", "bench.window", 10.0, 50.0, {}),
           ("/host:CPU", "python3", "engine.stepwise", 5.0, 12.0,
            {"rows": 8, "padded_positions": 512}),
           ("/host:CPU", "python3", "engine.harvest", 60.0, 70.0, {}),
           ("/host:CPU", "python3", "bench.pump", 5.0, 12.0, {}),
           ("/device:TPU:0", "XLA Ops", "fusion.1", 8.0, 20.0,
            {progtrace.SCOPE_STAT: "jit(f)/while/body/mlp/dot"}),
           ("/device:TPU:0", "XLA Ops", "fusion.2", 20.0, 30.0, {}),
           ("/device:TPU:0", "XLA Modules", "jit_f(1)", 8.0, 30.0, {})]
    prog = progtrace.reduce(raw)
    assert prog.window == (10.0, 50.0)
    assert [s[0] for s in prog.spans] == ["engine.stepwise"]
    assert prog.spans[0][1:3] == (5.0, 12.0)
    assert prog.scoped == [("mlp", 10.0, 20.0)]
    with pytest.raises(ValueError):
        progtrace.reduce(raw[1:])


def _pb(*fields) -> bytes:
    """A protobuf message: (number, int) as a varint, (number, bytes or
    str) as a length-delimited field, (number, None) as a fixed64."""
    def varint(n):
        out = bytearray()
        while True:
            out.append(n & 0x7F | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for number, v in fields:
        if v is None:
            out += varint(number << 3 | 1) + bytes(8)
        elif isinstance(v, int):
            out += varint(number << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(number << 3 | 2) + varint(len(v)) + v
    return out


def test_op_scopes_read_from_event_metadata(tmp_path):
    """The scope path is a stat of each operation's event *metadata*, as
    a string or as a reference to an interned string; operations without
    it, and planes that are not TPUs, give none."""
    def stat_meta(i, name):
        return (5, _pb((1, i), (2, _pb((1, i), (2, name)))))

    def event_meta(i, name, *stats):
        return (4, _pb((1, i), (2, _pb((1, i), (2, name), *(
            (5, _pb(*st)) for st in stats)))))
    tpu = _pb(
        (1, 3), (2, "/device:TPU:0"),
        (3, _pb((2, "XLA Ops"), (4, _pb((1, 1), (2, 10))))),   # a line
        stat_meta(7, "tf_op"), stat_meta(8, "flops"),
        stat_meta(9, "jit(f)/lm_head/dot_general:"),
        event_meta(1, "%fusion.1 = f(x)", [(1, 8), (2, None)],
                   [(1, 7), (5, "jit(f)/while/body/mlp/dot_general:")]),
        event_meta(2, "%fusion.2 = f(y)", [(1, 7), (7, 9)]),
        event_meta(3, "%copy.1 = copy(x)", [(1, 8), (3, 5)]))
    host = _pb((2, "/host:CPU"), stat_meta(7, "tf_op"),
               event_meta(1, "PjitFunction(f)", [(1, 7), (5, "jit(f)/x:")]))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, tpu), (1, host), (4, "hostname")))
    scopes = progtrace.op_scopes(str(path))
    assert scopes == {
        "%fusion.1 = f(x)": "jit(f)/while/body/mlp/dot_general:",
        "%fusion.2 = f(y)": "jit(f)/lm_head/dot_general:"}
    assert progtrace.scope_of(scopes["%fusion.1 = f(x)"]) == "mlp"


def _recorded():
    d = json.loads((DATA / "trace_phi3_backlog_program.json").read_text())
    raw = d["raw"]
    trace = devtrace.reduce([r[:5] for r in raw
                             if r[0].startswith("/device:")
                             or r[2].startswith(devtrace.HOST_PREFIX)])
    return d, trace, progtrace.reduce(raw)


def test_recorded_tpu_program_trace():
    """Two steps of a phi3-backlog window traced on a TPU v5e, with the
    stats as the chip wrote them: the reduction finds every scope and
    the spans' attributes, and the readers give what they gave there."""
    d, trace, prog = _recorded()
    assert {sc for sc, _, _ in prog.scoped} >= set(progtrace.SCOPES)
    steps = [st for n, _, _, st in prog.spans if n == "engine.stepwise"]
    assert steps and all(st["rows"] >= 1 and st["padded_positions"] >= 0
                         for st in steps)
    names = {n for n, _, _, _ in prog.spans}
    assert {"scheduler.pump", "engine.stepwise"} <= names
    ctx = _ctx(trace, prog)
    for name, want in d["expected"].items():
        got = run.read_metric(name, ctx)
        assert (got is None) == (want is None), name
        if want is not None:
            assert got == pytest.approx(want), name


def test_traced_run_hands_readers_program_events(monkeypatch):
    """A traced run (``run_cell`` with ``trace``) reads the program's spans
    from the trace before deleting it and passes them to every reader as
    ``ctx.program``: on the CPU at a tiny size the serving spans are
    there, and ``padded_position_share`` reads a value from them."""
    import dataclasses

    from perfbench import peaks
    from perfbench.tests.test_perfbench_faults import tiny_cell
    seen = []
    read_metric = run.read_metric

    def reading(name, ctx):
        seen.append((name, ctx.program))
        return read_metric(name, ctx)
    monkeypatch.setattr(run, "read_metric", reading)
    monkeypatch.setattr(run, "TRACE_S", 0.6)
    c = dataclasses.replace(tiny_cell("backlog"), per_layer=(
        {"name": "padded_position_share", "unit": "%"},))
    res = run.run_cell(c, 2**31 + 11, 0.6, True,
                       peaks.for_kind("TPU v5 lite"))
    assert res["correct"], res["checks"]
    (name, prog), = seen
    assert name == "padded_position_share"
    names = {n for n, _, _, _ in prog.spans}
    assert {"scheduler.pump", "engine.stepwise"} <= names
    share = res["metrics"]["padded_position_share"]["value"]
    assert 0 < share < 100


def test_traced_run_checks_the_whole_window(monkeypatch):
    """A traced run serves its whole window and profiles only its first
    ``TRACE_S``: the readers see the traced seconds and their completions,
    and the check samples from every completion of the window, as an
    untraced run's does.  The window closes at the fifth completion after
    the profiler has stopped, however long its stop stalls the host."""
    import dataclasses
    import time

    from perfbench import drive, peaks
    from perfbench.tests.test_perfbench_faults import tiny_cell
    seen, pools, mark = [], [], []
    read_metric, sample = run.read_metric, run.check.sample
    pump = drive.LoadGen.pump

    def counted(self):
        pump(self)
        if getattr(self.trace_hook, "state", None) != "done":
            return
        n = len(self.completed_in_window())
        mark[:] = mark or [n]
        if n >= mark[0] + 5:
            self.t_close = min(self.t_close, time.perf_counter() + 1e-9)

    def reading(name, ctx):
        seen.append(ctx)
        return read_metric(name, ctx)

    def sampling(done, k, seed):
        pools.append(list(done))
        return sample(done, k, seed)
    monkeypatch.setattr(run, "read_metric", reading)
    monkeypatch.setattr(run.check, "sample", sampling)
    monkeypatch.setattr(drive.LoadGen, "pump", counted)
    monkeypatch.setattr(run, "TRACE_S", 0.3)
    c = dataclasses.replace(tiny_cell("backlog"), per_layer=(
        {"name": "nfe_per_request", "unit": "calls"},))
    res = run.run_cell(c, 2**31 + 12, 120.0, True,
                       peaks.for_kind("TPU v5 lite"))
    assert res["correct"], res["checks"]
    (ctx,), (pool,) = seen, pools
    assert ctx.window_s == pytest.approx(0.3)
    assert 0 < len(ctx.completed) < len(pool) == res["attempted"]
    assert {id(r) for r in ctx.completed} <= {id(r) for r in pool}
    assert res["metrics"]["nfe_per_request"]["value"] > 0
