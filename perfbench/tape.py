"""The one traffic generator: a request tape from a traffic file and a seed.

Every seed gets the same work in another order.  The request lengths of
each segment are the same stratified multiset of the traffic's integer
range, permuted by the seed, and an open-loop segment holds a fixed
number of arrivals (``rate * seconds``), placed as a Poisson process
conditioned on that count: uniform order statistics over the segment.

Open loop (``"arrivals": "poisson"``): arrival offsets are seconds from
the opening of the measured window.  A lead-in segment before it brings
the rolling batch to a steady state, and a tail after it keeps the load
on while the window's last requests finish.

Backlog (``"arrivals": "backlog"``): no arrival times; the harness keeps
the queue topped up and takes lengths from :meth:`Tape.length` in order,
in blocks of ``max_batch`` lengths, each block the same stratified
multiset, so that any run of completions holds nearly the same mix.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**64, stream])


def stratified_lengths(n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` lengths spread evenly over the integers [lo, hi]: the
    midpoints of ``n`` equal slices of the range."""
    span = hi - lo + 1
    return lo + ((2 * np.arange(n) + 1) * span) // (2 * n)


@dataclasses.dataclass
class Tape:
    arrivals: np.ndarray | None     # (n,) seconds from window open; None = backlog
    lengths: np.ndarray             # (n,) for open loop; grows for backlog
    window_s: float
    _traffic: dict
    _seed: int

    @property
    def open_loop(self) -> bool:
        return self.arrivals is not None

    def length(self, i: int) -> int:
        """Length of the i-th request (backlog tapes grow on demand)."""
        while i >= len(self.lengths):
            t = self._traffic
            block = _rng(self._seed, 100 + len(self.lengths)).permutation(
                stratified_lengths(t["max_batch"], t["length_min"],
                                   t["length_max"]))
            self.lengths = np.concatenate([self.lengths, block])
        return int(self.lengths[i])

    def in_window(self) -> np.ndarray:
        """Open loop: which requests are scheduled inside the window."""
        return (self.arrivals >= 0) & (self.arrivals < self.window_s)


def make(traffic: dict, seed: int, seconds: float) -> Tape:
    kind = traffic["arrivals"]
    lo, hi = traffic["length_min"], traffic["length_max"]
    if kind == "backlog":
        return Tape(None, np.zeros(0, np.int64), float(seconds), traffic,
                    seed)
    if kind != "poisson":
        raise ValueError(f"unknown arrivals {kind!r}")
    rate = float(traffic["rate_per_s"])
    segments = [(-float(traffic["lead_in_s"]), 0.0),
                (0.0, float(seconds)),
                (float(seconds), float(seconds) + float(traffic["tail_s"]))]
    arrivals, lengths = [], []
    for k, (a, b) in enumerate(segments):
        n = max(1, int(round(rate * (b - a))))
        rng = _rng(seed, k)
        arrivals.append(np.sort(rng.uniform(a, b, n)))
        lengths.append(rng.permutation(stratified_lengths(n, lo, hi)))
    return Tape(np.concatenate(arrivals), np.concatenate(lengths),
                float(seconds), traffic, seed)
