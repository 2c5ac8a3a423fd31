"""Analyzer property coverage (ISSUE 7 satellite): ``balanced`` never loses
to ``greedy`` under the Scheduler's own makespan model, ``force_queue``
routes every task as documented, and a measured ``CalibratedModel`` with
swapped engine speeds flips STQ/DTQ assignments."""
import dataclasses

import numpy as np
import pytest

from repro.core import analyzer, scheduler
from repro.core.calibrate import CalibratedModel
from repro.core.partition import make_tasks
from repro.core.perfmodel import (TPUV5E, VCK5000, HardwareModel,
                                  runtime_fallback)


def _random_part(rng, name="k"):
    nrt = int(rng.integers(1, 9))
    nct = int(rng.integers(1, 5))
    tm, tn = 64, 32
    K = int(rng.integers(1, 17)) * 64
    row_d = rng.uniform(1e-4, 1.0, size=nrt)
    col_d = rng.uniform(1e-4, 1.0, size=nct)
    return make_tasks(name, nrt * tm, K, nct * tn, row_d, col_d, tm, tn)


def _hw_variants():
    yield VCK5000
    yield TPUV5E
    # stress the LPT-vs-greedy race: few sparse units, tight bandwidth
    yield dataclasses.replace(VCK5000, name="v-1unit", n_sparse_units=1)
    yield dataclasses.replace(VCK5000, name="v-slowmem", mem_bw=1e9)
    yield dataclasses.replace(
        VCK5000, name="v-overhead", dispatch_overhead=1e-5,
        n_sparse_units=2)


@pytest.mark.parametrize("seed", range(8))
def test_property_balanced_never_worse_than_greedy(seed):
    """The ``balanced`` strategy simulates both its LPT placement and the
    per-task greedy rule and returns the better one — so its modeled
    makespan is ≤ greedy's for ANY task set and ANY hardware model."""
    rng = np.random.default_rng(seed)
    for hw in _hw_variants():
        part = _random_part(rng)
        g_stq, g_dtq = analyzer.analyze_kernel(part, hw, "greedy")
        greedy_ms = scheduler.simulate(g_stq, g_dtq, hw).makespan
        b_stq, b_dtq = analyzer.analyze_kernel(part, hw, "balanced")
        balanced_ms = scheduler.simulate(b_stq, b_dtq, hw).makespan
        assert balanced_ms <= greedy_ms * (1 + 1e-12), (hw.name, seed)
        # the returned lists and the task fields agree
        assert all(t.queue == "STQ" for t in b_stq)
        assert all(t.queue == "DTQ" for t in b_dtq)
        assert len(b_stq) + len(b_dtq) == len(part.tasks)


def test_force_queue_routes_every_task():
    rng = np.random.default_rng(3)
    part = _random_part(rng)
    stq, dtq = analyzer.force_queue(part, VCK5000, "STQ")
    assert not dtq and len(stq) == len(part.tasks)
    assert all(t.queue == "STQ" for t in stq)
    assert all(t.primitive in ("SpDMM", "SpMM") for t in stq)
    stq, dtq = analyzer.force_queue(part, VCK5000, "DTQ")
    assert not stq and len(dtq) == len(part.tasks)
    assert all(t.queue == "DTQ" and t.primitive == "GEMM" for t in dtq)


def _calibrated(name, *, gemm_rate, sparse_rate):
    """A CalibratedModel with explicit engine rates (MAC/s) and memory so
    fast that compute decides every assignment."""
    return CalibratedModel(
        name=name, f_dense=1.0, dense_macs_per_cycle=gemm_rate,
        f_sparse=1.0, spdmm_macs_per_cycle=sparse_rate,
        spmm_macs_per_cycle=sparse_rate, n_sparse_units=1,
        mem_bw=1e18, bytes_per_elem=4, dispatch_overhead=0.0,
        skip_block=1, calibrated=True, backend="test", block=8,
        dtype="float32", base="test")


def test_calibrated_swapped_speeds_flip_assignments():
    """Swapping the measured dense/sparse rates of a CalibratedModel must
    flip the greedy STQ/DTQ split: what a fast dense engine claimed, a
    fast sparse engine claims instead."""
    part_args = ("k", 256, 512, 64, [0.5, 0.5, 0.5, 0.5], [0.5], 64, 64)
    fast_dense = _calibrated("cal-dense", gemm_rate=1e12, sparse_rate=1e6)
    stq, dtq = analyzer.analyze_kernel(
        make_tasks(*part_args), fast_dense, "greedy")
    assert not stq and len(dtq) == 4

    fast_sparse = _calibrated("cal-sparse", gemm_rate=1e6, sparse_rate=1e12)
    stq, dtq = analyzer.analyze_kernel(
        make_tasks(*part_args), fast_sparse, "greedy")
    assert not dtq and len(stq) == 4

    # balanced follows the same measurement signal
    stq, dtq = analyzer.analyze_kernel(
        make_tasks(*part_args), fast_sparse, "balanced")
    assert len(stq) == 4 and not dtq


def test_calibrated_model_is_a_hardware_model():
    """CalibratedModel slots into every HardwareModel consumer; provenance
    flags distinguish fitted models from fallback guesses."""
    m = _calibrated("cal", gemm_rate=1e9, sparse_rate=1e9)
    assert isinstance(m, HardwareModel)
    assert m.calibrated and not m.fallback
    assert TPUV5E.fallback and not TPUV5E.calibrated
    assert not VCK5000.fallback
    fb = runtime_fallback("cpu")
    assert fb.fallback and fb.name == "cpu-fallback"
    assert runtime_fallback("TPU v5 lite") is TPUV5E
    assert runtime_fallback() is fb           # this host's own kind: "cpu"
    # another chip generation must not borrow v5e's constants
    with pytest.raises(ValueError, match="no hardware model"):
        runtime_fallback("TPU v4")
