"""The reduction from a profiler trace to busy time, op times and idle gaps,
checked against hand sums on a small hand-made trace and on a slice of a
trace recorded on a TPU v5e (``trace_fixture.json``)."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import devtrace as tr  # noqa: E402

# nanoseconds; the window is [0, 40)
EVENTS = {
    "ops": [["fusion.1", 0, 10], ["custom-call", 5, 10], ["fusion.1", 20, 10],
            ["copy", 45, 5]],
    "modules": [["jit_replay(7)", 0, 16], ["jit_concatenate(3)", 18, 14]],
    "host": [[tr.WINDOW, 0, 40, "python"],
             ["PjitFunction(replay)", 13, 9, "serving-dispatch"],
             ["np.asarray(jax.Array)", 29, 11, "python"]],
}


def test_union_and_cover():
    assert tr.union([(5, 8), (0, 3), (2, 4), (8, 9)]) == [(0, 4), (5, 9)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert tr.covered([(0, 4), (5, 9)], [(3, 6), (8, 20)]) == 1 + 1 + 1


def test_busy_and_window():
    lo, hi = tr.window_span(EVENTS)
    assert (lo, hi) == (0, 40)
    assert tr.busy(EVENTS, lo, hi) == [(0, 15), (20, 30)]


def test_program_busy_counts_ops_inside_its_modules():
    assert tr.program_busy(EVENTS, 0, 40, "jit_replay") == 15
    assert tr.program_busy(EVENTS, 0, 40, "jit_concatenate") == 10


def test_op_seconds_ranks_by_device_time():
    assert tr.op_seconds(EVENTS, 0, 40) == [["fusion.1", 20e-9],
                                           ["custom-call", 10e-9]]


def test_idle_gaps_are_labelled_by_the_host():
    assert tr.idle_gaps(EVENTS, 0, 40) == [
        ["np.asarray(jax.Array)", 10e-9], ["PjitFunction(replay)", 5e-9]]


def test_missing_window_raises():
    with pytest.raises(ValueError, match="no 'bench.window' span"):
        tr.window_span({"host": []})


FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "trace_fixture.json")


def test_recorded_trace():
    with open(FIXTURE) as f:
        ev = json.load(f)
    lo, hi = ev.pop("window")
    busy = tr.busy(ev, lo, hi)
    # by hand: every op interval clipped to the slice, overlaps merged
    ivs = sorted((max(s, lo), min(s + d, hi)) for _, s, d in ev["ops"]
                 if s + d > lo and s < hi)
    total, end = 0.0, lo
    for s, e in ivs:
        if e > end:
            total += e - max(s, end)
            end = e
    assert sum(e - s for s, e in busy) == pytest.approx(total)
    assert 0 < total < hi - lo
    ops = tr.op_seconds(ev, lo, hi, top=10_000)
    assert sum(s for _, s in ops) * 1e9 >= total * (1 - 1e-9)
    gaps = tr.idle_gaps(ev, lo, hi, top=10_000)
    assert sum(s for _, s in gaps) * 1e9 == pytest.approx(hi - lo - total)
    assert tr.program_busy(ev, lo, hi, "jit_replay") <= total
