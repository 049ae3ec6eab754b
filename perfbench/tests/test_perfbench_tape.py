"""The traffic generator: deterministic from the seed, same work for
every seed."""
import json

import numpy as np

from perfbench import cell, tape

POISSON = cell.load_json(cell.HERE / "traffic"
                         / "poisson-t50-b8-r27.json")
BACKLOG = cell.load_json(cell.HERE / "traffic" / "backlog-t50-b8.json")
BIG_SEED = 2**31 + 987_654_321


def test_same_seed_same_tape():
    a, b = tape.make(POISSON, BIG_SEED, 30), tape.make(POISSON, BIG_SEED, 30)
    np.testing.assert_array_equal(a.arrivals, b.arrivals)
    np.testing.assert_array_equal(a.lengths, b.lengths)
    c = tape.make(POISSON, BIG_SEED + 1, 30)
    assert not np.array_equal(a.arrivals, c.arrivals)


def test_every_seed_gets_the_same_work():
    rate, secs = POISSON["rate_per_s"], 30
    tapes = [tape.make(POISSON, s, secs) for s in (1, 2, BIG_SEED)]
    for t in tapes:
        win = t.in_window()
        assert win.sum() == round(rate * secs)
        assert (np.diff(t.arrivals) >= 0).all()
        assert t.arrivals[0] >= -POISSON["lead_in_s"]
        assert t.lengths.min() >= POISSON["length_min"]
        assert t.lengths.max() <= POISSON["length_max"]
    multisets = [np.sort(t.lengths[t.in_window()]) for t in tapes]
    for m in multisets[1:]:
        np.testing.assert_array_equal(m, multisets[0])


def test_stratified_lengths_cover_the_range():
    got = tape.stratified_lengths(129, 128, 256)
    np.testing.assert_array_equal(got, np.arange(128, 257))


def test_backlog_lengths_deterministic_and_growing():
    a, b = tape.make(BACKLOG, 5, 30), tape.make(BACKLOG, 5, 30)
    assert not a.open_loop
    la = [a.length(i) for i in range(200)]
    assert la == [b.length(i) for i in range(200)]
    k = BACKLOG["max_batch"]
    first = np.sort(la[:k])
    second = np.sort(la[k:2 * k])
    np.testing.assert_array_equal(first, second)


def test_traffic_files_parse():
    for p in (cell.HERE / "traffic").glob("*.json"):
        t = json.loads(p.read_text())
        tape.make(t, 3, 10)
