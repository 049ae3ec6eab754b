"""The trace reduction, on a hand-made trace whose answers are known and
on a small trace recorded on a TPU v5e (a traced text8-poisson run)."""
import json
import pathlib

import pytest

from perfbench import devtrace

DATA = pathlib.Path(__file__).with_name("data")
MS = 1_000_000.0


def hand_trace() -> devtrace.Trace:
    # window 0..100 ms; step program runs 10-20, 30-40 and 70-80 ms;
    # its ops overlap inside it; a scatter at 45-47; the harness waited
    # for arrivals from 50 to 68 ms and pumped in between
    ops = [("fusion.1", 10 * MS, 15 * MS), ("decode_scores.1", 14 * MS,
                                             20 * MS),
           ("fusion.1", 30 * MS, 35 * MS), ("decode_scores.1", 35 * MS,
                                             40 * MS),
           ("scatter.2", 45 * MS, 47 * MS),
           ("fusion.1", 70 * MS, 76 * MS), ("decode_scores.1", 76 * MS,
                                             80 * MS)]
    modules = [("jit__dndm_rows(7)", 10 * MS, 20 * MS),
               ("jit__dndm_rows(7)", 30 * MS, 40 * MS),
               ("jit_scatter(3)", 45 * MS, 47 * MS),
               ("jit__dndm_rows(7)", 70 * MS, 80 * MS)]
    host = [("bench.window", 0.0, 100 * MS),
            ("bench.pump", 5 * MS, 21 * MS), ("bench.pump", 21 * MS,
                                               41 * MS),
            ("bench.submit", 41 * MS, 45 * MS),
            ("bench.wait", 50 * MS, 68 * MS),
            ("bench.pump", 68 * MS, 81 * MS)]
    return devtrace.Trace(ops=ops, modules=modules, host=host,
                          window=(0.0, 100 * MS))


def test_busy_union_and_idle_share():
    tr = hand_trace()
    assert devtrace.union_ns([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4
    assert devtrace.busy_s(tr) == pytest.approx(0.032)
    gaps = devtrace.idle_gaps(tr)
    assert [(a / MS, b / MS) for a, b in gaps] == [
        (0, 10), (20, 30), (40, 45), (47, 70), (80, 100)]


def test_kernel_time_by_name():
    total, n = devtrace.op_time_ns(hand_trace(), "decode_scores")
    assert n == 3 and total == pytest.approx(15 * MS)


def test_step_program_and_gaps():
    tr = hand_trace()
    prog = devtrace.main_program(tr)
    assert prog == "jit__dndm_rows(7)"
    # 20->30 counts; 40->70 spans a wait for arrivals and is left out
    assert devtrace.step_gaps_ns(tr, prog) == [10 * MS]
    assert devtrace.mean_step_gap_ms(tr) == pytest.approx(10.0)


def test_idle_gaps_named_by_host_span():
    top = devtrace.attribute_gaps(hand_trace(), top=3)
    assert top[0] == ["bench.wait", pytest.approx(0.023)]
    assert top[1][1] == pytest.approx(0.020)
    assert devtrace.top_ops(hand_trace(), top=1) == [
        ["fusion.1", pytest.approx(0.016)]]


def test_recorded_tpu_trace():
    d = json.loads((DATA / "trace_text8_poisson.json").read_text())
    tr = devtrace.reduce(d["raw"])
    want = d["expected"]
    assert tr.window_s == pytest.approx(want["window_s"])
    assert want["main_program"].startswith("jit__dndm_rows")
    assert 0 < devtrace.busy_s(tr) <= tr.window_s
    assert devtrace.busy_s(tr) == pytest.approx(want["busy_s"])
    assert devtrace.main_program(tr) == want["main_program"]
    assert devtrace.mean_step_gap_ms(tr) == pytest.approx(
        want["mean_step_gap_ms"])
    total, n = devtrace.op_time_ns(tr, "decode_scores")
    assert n == want["decode_scores_count"]
    assert total == pytest.approx(want["decode_scores_ns"])
    assert n >= 1
    ops = {name for name, _, _ in tr.ops}
    assert all(" " not in name and not name.startswith("%") for name in ops)


def test_metric_readers_on_hand_trace():
    import types

    from perfbench import cell, flops, peaks, run
    conf = cell.load_json(cell.HERE / "configs" / "phi3-mini-3.8b.json")
    traffic = {"max_batch": 8, "canvas": 256, "x0_mode": "sample"}
    pk = peaks.for_kind("TPU v5 lite")
    ctx = types.SimpleNamespace(trace=hand_trace(), conf=conf,
                                traffic=traffic, peaks=pk, flops=flops,
                                devtrace=devtrace, live_rows=[8, 5, 8])
    read = lambda name: run.read_metric(name, ctx)  # noqa: E731
    assert read("step_device_ms") == pytest.approx(10.0)
    assert read("call_gap_ms.backlog") == pytest.approx(10.0)
    assert read("device_idle_share") == pytest.approx(68.0)
    work = (8 + 5 + 8) * flops.denoiser_flops(conf, 256)
    assert read("step_mfu") == pytest.approx(100 * work / (0.1 * 197e12))
    least = flops.roofline_seconds(
        *flops.decode_scores_work(8, 256, 32064, "bfloat16", True), pk)[0]
    assert read("decode_scores_roofline") == pytest.approx(
        100 * least / 5e-3)


def test_step_mfu_pairs_executions_with_dispatches():
    """Executions pair with dispatches in order, counting those that ended
    before the window; one that started before it is not the window's."""
    import types

    from perfbench import cell, flops, peaks, run
    prog = "jit__dndm_rows(7)"
    raw = [("/host:CPU", "", "bench.window", 100 * MS, 200 * MS)] + [
        ("/device:TPU:0", "XLA Modules", prog, s * MS, (s + 10) * MS)
        for s in (10, 40, 95, 120, 150)]
    conf = cell.load_json(cell.HERE / "configs" / "dndm-text8.json")
    ctx = types.SimpleNamespace(
        trace=devtrace.reduce(raw), conf=conf, devtrace=devtrace,
        traffic={"canvas": 256}, peaks=peaks.for_kind("TPU v5 lite"),
        flops=flops, live_rows=[1, 2, 4, 8, 16])
    assert ctx.trace.modules_before == {prog: 2}
    work = (8 + 16) * flops.denoiser_flops(conf, 256)
    assert run.read_metric("step_mfu", ctx) == pytest.approx(
        100 * work / (0.1 * 197e12))
    ctx.live_rows = ctx.live_rows[:4]
    assert run.read_metric("step_mfu", ctx) is None


def test_live_rows_count_dispatched_calls_only():
    """``step_mfu`` pairs the trace's executions with the live-row counts
    in order, so a ``step()`` that dispatches no call (a drain: no live
    row) must add no count."""
    from perfbench import drive

    class Runner:
        def __init__(self):
            self.live = [2, 1, 0, 3]

        def active_rows(self):
            return list(range(self.live[0]))

        def step(self):
            self.live.pop(0)
            return {}

    class Engine:
        def stepwise(self, *a, **k):
            return Runner()

    engine = Engine()
    load = drive.LoadGen(None, engine, None, max_batch=4)
    runner = engine.stepwise(4, 16)
    for _ in range(4):
        runner.step()
    assert load.live_rows == [2, 1, 3]
