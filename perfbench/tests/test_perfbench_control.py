"""The control: the plain reference put in the program's place at the
precision below the configuration's (bfloat16 for dndm-text8's float32,
fp8 matmul operands for Phi-3's bfloat16), read on the same served
canvases and noise and judged by the run's own checks: it fails both the
widest and the mean gap.

On the chip it runs at the cells' own sizes (``perfbench/calibrate.py
readings``).  Here it runs at a size a test can hold, on the CPU, where
the program computes in exact float32 and agrees with the float32
reference at ``HIGHEST``, so the program's widest gap is rounding, and the
control's is not."""
import dataclasses
import time

import pytest

from perfbench import drive, peaks, run
from perfbench.tests.test_perfbench_faults import tiny_cell

COMPLETIONS = 60    # the window closes at this many completed requests


def close_at_completions(monkeypatch, n: int) -> None:
    """Close a backlog's window at its ``n``-th completion instead of on
    the clock.  A backlog completes its requests in the same order at any
    host speed, so the requests the check samples from are the same on a
    loaded host and an idle one."""
    pump = drive.LoadGen.pump

    def counted(self):
        pump(self)
        if self.t_open and len(self.completed_in_window()) >= n:
            self.t_close = min(self.t_close, time.perf_counter() + 1e-9)
    monkeypatch.setattr(drive.LoadGen, "pump", counted)


@pytest.mark.parametrize("control", ["bfloat16", "fp8"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_reads_far_above_the_program(monkeypatch, seed, control):
    close_at_completions(monkeypatch, COMPLETIONS)
    c = tiny_cell("backlog")
    conf = dict(c.config, check=dict(c.config["check"], requests=16,
                                     control=control))
    traffic = dict(c.traffic, canvas=64, length_min=32, length_max=64)
    c = dataclasses.replace(c, config=conf, traffic=traffic)
    res = run.run_cell(c, seed, 120.0, False, peaks.for_kind("TPU v5 lite"),
                       control=True)
    assert res["attempted"] >= COMPLETIONS
    assert res["correct"], res["checks"]
    program = res["checks"]["logit_gap"]["value"]
    ctrl = res["control"]["checks"]["logit_gap"]["value"]
    print(f"seed {seed} {control}: program {program} control {ctrl}")
    assert program < 1e-4
    assert ctrl > 30 * max(program, 1e-5)
    assert not res["control"]["correct"], res["control"]["checks"]
    mean = res["control"]["checks"]["mean_logit_gap"]
    assert res["checks"]["mean_logit_gap"]["value"] == 0.0
    assert mean["value"] > mean["limit"], res["control"]["checks"]
