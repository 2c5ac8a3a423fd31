"""Every metric reader of BENCHMARK.json on a hand-made run, against hand
sums: two micro-batches of two requests each, the second straddling the
window's close, and a trace with one op in each batch."""
import dataclasses
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import costs  # noqa: E402
import devtrace as tr  # noqa: E402
import harness  # noqa: E402
import load  # noqa: E402

PEAK = {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e9}


@dataclasses.dataclass
class Stats:                       # the fields of RequestStats that are read
    t_queue: float
    t_execute: float
    error: str | None = None


def _rec(t_due, t_ready, t_done):
    r = load.Record(idx=0, t_due=t_due, t_ready=t_ready, t_done=t_done)
    r.logits = [[0.0]]
    return r


def _run(events=None) -> harness.Run:
    # window [100, 110) on the host clock; batch A runs [101, 103), batch B
    # runs [108, 112) and is held by its clients at 112
    recs = [_rec(100.0, 103.0, 103.0), _rec(100.5, 103.0, 103.0),
            _rec(104.0, 112.0, 112.0), _rec(105.0, 112.0, 112.0)]
    stats = [Stats(1.0, 2.0), Stats(0.5, 2.0), Stats(4.0, 4.0),
             Stats(3.0, 4.0)]
    act = [{"stored_blocks": 30, "logical_blocks": 100},
           {"stored_blocks": 50, "logical_blocks": 100}]
    return harness.Run(
        cell="x", seconds=10.0, setup_s=12.5, recs=recs,
        edges={"t_start": 100.0, "t_end": 110.0}, requests=stats,
        activation=act,
        kernels=lambda k: [costs.dense("l1", k * 10, 100, 10)],
        peak=PEAK, events=events)


def _read(name, run):
    return harness._module(harness.BENCH / "metrics" / f"{name}.py").read(run)


def test_req_per_s_counts_the_straddling_batch_by_its_share():
    # two done, two in flight at the close: (110 - 108) / (112 - 108) each
    assert _read("req_per_s", _run()) == pytest.approx((2 + 2 * 0.5) / 10)


def test_program_spans():
    run = _run()
    assert run.batches() == [(101.0, 103.0, 2), (108.0, 112.0, 2)]
    assert _read("batch_ms.sat", run) == pytest.approx(3000)
    assert _read("setup_s", run) == 12.5


def test_counters_and_shares_of_peak():
    run = _run()
    assert _read("act_skipped_ratio", run) == pytest.approx(60.0)
    ops = 2 * 10 * 100 * 10
    assert _read("mfu.sat", run) == pytest.approx(100 * 3 * ops / 10 / 1e12)
    run.activation = []
    assert _read("act_skipped_ratio", run) is None


def test_trace_metrics():
    # trace clock = host clock + 1e9 ns (the window's span opens at 100 s)
    off = 1e9
    ns = lambda t: t * 1e9 + off  # noqa: E731
    events = {
        "ops": [["spdmm_fused", ns(101.5), 1e9], ["gemm", ns(108.0), 1e9]],
        "modules": [["jit_replay(1)", ns(101.0), 2e9],
                    ["jit_replay(1)", ns(108.0), 2e9]],
        "host": [[tr.WINDOW, ns(100.0), 10e9, "python3"]]}
    run = _run(events)
    assert _read("idle_share.sat", run) == pytest.approx(80.0)
    least = costs.dense("l1", 20, 100, 10).least_s(PEAK)
    # batch B lies half inside the window
    want = 100 * least * 1.5 / 2.0
    assert _read("kernel_roofline.sat", run) == pytest.approx(want)


def test_trace_metrics_are_silent_without_a_trace():
    run = _run()
    for name in ("idle_share.sat", "kernel_roofline.sat"):
        assert _read(name, run) is None
    run.peak = {}
    assert _read("mfu.sat", run) is None
