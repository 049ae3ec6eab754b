"""Drive the served path from a tape: open-loop arrivals or a backlog.

One thread does everything, as a serving loop would: it submits the
requests that are due, pumps the scheduler (one batched network call per
pump), harvests what finished, and sleeps until the next arrival when
nothing is live.  Each of these is a ``jax.profiler.TraceAnnotation``
(``bench.submit``, ``bench.pump``, ``bench.harvest``, ``bench.wait``), so
a traced run can say what the host was doing in each device-idle gap.

Times are ``time.perf_counter()`` seconds.  A request's latency runs from
its *scheduled* arrival to the harvest that saw it complete, so a late
generator or a stalled pump is charged to the requests it delayed.
"""
from __future__ import annotations

import dataclasses
import itertools
import time

import numpy as np
from jax.profiler import TraceAnnotation

DRAIN_S = 60.0              # how long past the window a request may finish
DEPTH = 2                   # a backlog's queue, in batches of max_batch


@dataclasses.dataclass
class Rec:
    """One request as the harness saw it."""
    idx: int                    # position on the tape
    length: int
    scheduled: float            # scheduled arrival (backlog: submit time)
    submitted: float = 0.0      # when submit() was called
    submit_s: float = 0.0       # how long submit() took
    done: float | None = None   # harvest time of its completion
    req: object = None          # the scheduler's Request
    canvas: np.ndarray | None = None    # the whole canvas it finished on


class LoadGen:
    def __init__(self, sched, engine, tape, *, max_batch: int,
                 nfe_mean: float = 1.0):
        self.sched = sched
        self.tape = tape
        self.max_batch = max_batch
        self.depth = DEPTH * max_batch
        # backlog lead-in: admit one request every this many calls, so the
        # rows' finishing times are spread over a request's life
        self.stagger = max(1, round(nfe_mean / max_batch))
        self.recs: dict[int, Rec] = {}          # rid -> Rec
        self.next = 0                           # next tape index
        self.in_flight = 0                      # submitted, not done
        self.t_open = self.t_close = 0.0
        self.trace_hook = None                  # called once per loop turn
        self.live_rows: list[int] = []          # live rows of each call
        self._finished: list[np.ndarray] = []   # whole rows, this pump
        self._wrap_runners(engine)

    def _wrap_runners(self, engine) -> None:
        """Count the live rows of every call in the order the calls are
        dispatched (a ``step()`` with no live row dispatches none), and
        keep the whole canvas of each finished row: the scheduler hands
        back each request's first ``length`` tokens, and the check needs
        every position the denoiser saw."""
        make = engine.stepwise

        def stepwise(*a, **k):
            runner = make(*a, **k)
            step = runner.step

            def kept_step():
                rows = len(runner.active_rows())
                if rows:
                    self.live_rows.append(rows)
                done = step()
                self._finished.extend(done.values())
                return done
            runner.step = kept_step
            return runner
        engine.stepwise = stepwise

    # ---------------- the three host actions ----------------

    def submit(self, scheduled: float) -> None:
        length = self.tape.length(self.next)
        t0 = time.perf_counter()
        with TraceAnnotation("bench.submit"):
            rid = self.sched.submit(length)
        t1 = time.perf_counter()
        self.recs[rid] = Rec(self.next, length, scheduled, t0, t1 - t0)
        self.next += 1
        self.in_flight += 1

    def pump(self) -> None:
        n_done = len(self.sched.done)
        with TraceAnnotation("bench.pump"):
            self.sched.pump()
        with TraceAnnotation("bench.harvest"):
            now = time.perf_counter()
            new = len(self.sched.done) - n_done
            rows, self._finished = self._finished, []
            if not new:
                return
            for r in itertools.islice(reversed(self.sched.done.values()),
                                      new):
                rec = self.recs[r.rid]
                rec.done, rec.req = now, r
                res = np.asarray(r.result)
                for row in rows:
                    if (len(row) >= len(res)
                            and np.array_equal(row[:len(res)], res)):
                        rec.canvas = row
                        break
            self.in_flight -= new

    def wait_until(self, t: float) -> None:
        with TraceAnnotation("bench.wait"):
            dt = t - time.perf_counter()
            if dt > 0:
                time.sleep(dt)

    # ---------------- the two traffic shapes ----------------

    def _turn(self) -> float:
        """Once per loop turn: let the tracer look at the clock."""
        now = time.perf_counter()
        if self.trace_hook is not None:
            self.trace_hook(now)
        return now

    def run_open_loop(self, seconds: float) -> None:
        """Arrivals at their scheduled times; the window opens after the
        tape's lead-in and the run ends when every request scheduled in
        the window has finished, or ``DRAIN_S`` after the window closes."""
        arr = self.tape.arrivals
        start = time.perf_counter()
        self.t_open = start - float(arr[0])
        self.t_close = self.t_open + seconds
        due = self.t_open + arr
        window = set(np.nonzero(self.tape.in_window())[0].tolist())
        n = len(arr)
        while True:
            now = self._turn()
            while self.next < n and due[self.next] <= now:
                self.submit(float(due[self.next]))
            if now >= self.t_close:
                open_ = [r for r in self.recs.values()
                         if r.idx in window and r.done is None]
                if (not open_ and self.next > max(window)) or (
                        now > self.t_close + DRAIN_S):
                    break
            if self.in_flight:
                self.pump()
            elif self.next < n:
                self.wait_until(float(due[self.next]))
            else:
                break

    def run_backlog(self, seconds: float) -> None:
        """Keep the queue at ``depth`` through the window.

        A full queue from the start would admit every row at once, and
        rows with a similar number of calls would then finish in waves, a
        batch at a time, for many lifetimes: the count a window catches
        would hang on where its edges fall among the waves.  So the
        lead-in admits the rows one at a time, ``stagger`` calls apart,
        which spreads their finishing times over a request's life as in a
        server that has run for a while; the window opens at the first
        completion after that."""
        def top_up():
            while len(self.sched.queue) < self.depth:
                self.submit(time.perf_counter())
        for _ in range(self.max_batch):
            self.submit(time.perf_counter())
            for _ in range(self.stagger):
                self.pump()
        while not any(r.done is not None for r in self.recs.values()):
            top_up()
            self.pump()
        self.t_open = time.perf_counter()
        self.t_close = self.t_open + seconds
        while True:
            now = self._turn()
            if now >= self.t_close:
                break
            top_up()
            self.pump()

    # ---------------- what the window saw ----------------

    def completed_in_window(self) -> list[Rec]:
        return [r for r in self.recs.values() if r.done is not None
                and self.t_open <= r.done < self.t_close]

    def scheduled_in_window(self) -> list[Rec]:
        return [r for r in self.recs.values()
                if self.t_open <= r.scheduled < self.t_close]
