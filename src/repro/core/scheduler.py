"""Scheduler — Algorithm 4 lines 13-21, as a simulator.

``simulate``: event-driven list scheduling of the two task queues onto the
sparse units (8 ALU arrays on VCK5000) and the single dense engine (AIE
array / MXU), exactly the paper's idle-unit pop loop.  Returns makespan and
per-unit busy time — this is the cycle-estimate backend of the benchmark
harness (the paper's own evaluation methodology: a perf-model-driven
simulator with a DDR bandwidth bound, §IV-A).  ``simulate_sharded`` does the
same per device of a placed plan.

The queues themselves are drained by the lowered dispatches of
``repro.core.dispatch`` and ``repro.core.shard_exec``
(``DynasparseEngine.route``).
"""
from __future__ import annotations

import dataclasses
import heapq

from repro.core.partition import Task
from repro.core.perfmodel import HardwareModel, flops, data_count


@dataclasses.dataclass
class ScheduleReport:
    makespan: float                 # seconds (hardware execution time)
    t_sparse_busy: float            # Σ busy time over sparse units
    t_dense_busy: float             # busy time of the dense engine
    n_stq: int
    n_dtq: int
    n_spdmm: int
    n_spmm: int
    flops_executed: float
    flops_dense_equiv: float        # FLOPs had every task run as GEMM
    data_loaded: float              # elements (Table V "#Data")
    data_dense_equiv: float
    memory_time: float              # total bytes / BW (bandwidth bound)
    # sharded plans: one sub-report per mesh device (empty when unsharded).
    # The scalar fields above stay the combined view (makespan = slowest
    # device; busy/flops/data = totals) so existing consumers are unchanged.
    per_device: tuple = ()

    @classmethod
    def zero(cls) -> "ScheduleReport":
        """Identity element of ``merge`` — the report of zero kernels."""
        return cls(makespan=0.0, t_sparse_busy=0.0, t_dense_busy=0.0,
                   n_stq=0, n_dtq=0, n_spdmm=0, n_spmm=0,
                   flops_executed=0.0, flops_dense_equiv=0.0,
                   data_loaded=0.0, data_dense_equiv=0.0, memory_time=0.0)

    def merge(self, other: "ScheduleReport") -> "ScheduleReport":
        per_device: tuple = ()
        if self.per_device or other.per_device:
            a, b = list(self.per_device), list(other.per_device)
            n = max(len(a), len(b))
            a += [ScheduleReport.zero()] * (n - len(a))
            b += [ScheduleReport.zero()] * (n - len(b))
            per_device = tuple(x.merge(y) for x, y in zip(a, b))
        return ScheduleReport(
            makespan=self.makespan + other.makespan,
            t_sparse_busy=self.t_sparse_busy + other.t_sparse_busy,
            t_dense_busy=self.t_dense_busy + other.t_dense_busy,
            n_stq=self.n_stq + other.n_stq,
            n_dtq=self.n_dtq + other.n_dtq,
            n_spdmm=self.n_spdmm + other.n_spdmm,
            n_spmm=self.n_spmm + other.n_spmm,
            flops_executed=self.flops_executed + other.flops_executed,
            flops_dense_equiv=self.flops_dense_equiv + other.flops_dense_equiv,
            data_loaded=self.data_loaded + other.data_loaded,
            data_dense_equiv=self.data_dense_equiv + other.data_dense_equiv,
            memory_time=self.memory_time + other.memory_time,
            per_device=per_device,
        )

    def scaled(self, s: float) -> "ScheduleReport":
        """Cost fields scaled by ``s`` — the per-request attribution the
        serving layer uses for a micro-batch share.  The task / primitive
        counts describe the shared fused launches and are left intact."""
        return dataclasses.replace(
            self,
            makespan=self.makespan * s,
            t_sparse_busy=self.t_sparse_busy * s,
            t_dense_busy=self.t_dense_busy * s,
            flops_executed=self.flops_executed * s,
            flops_dense_equiv=self.flops_dense_equiv * s,
            data_loaded=self.data_loaded * s,
            data_dense_equiv=self.data_dense_equiv * s,
            memory_time=self.memory_time * s,
            per_device=tuple(r.scaled(s) for r in self.per_device),
        )


def simulate(stq: list[Task], dtq: list[Task], hw: HardwareModel) -> ScheduleReport:
    """List-schedule STQ onto ``hw.n_sparse_units`` ALU arrays and DTQ onto
    the dense engine; makespan = max(compute makespan, memory time)."""
    # sparse units: min-heap of available times
    sparse_free = [0.0] * hw.n_sparse_units
    heapq.heapify(sparse_free)
    sparse_busy = 0.0
    for task in stq:
        t0 = heapq.heappop(sparse_free)
        heapq.heappush(sparse_free, t0 + task.t_sparse)
        sparse_busy += task.t_sparse
    sparse_makespan = max(sparse_free) if sparse_free else 0.0

    dense_busy = sum(t.t_dense for t in dtq)

    # Both engines run concurrently (PL ∥ AIE): compute makespan is the max.
    compute_makespan = max(sparse_makespan, dense_busy)

    f_exec = sum(flops(t.shape, t.primitive) for t in stq + dtq)
    f_dense = sum(flops(t.shape, "GEMM") for t in stq + dtq)
    d_load = sum(data_count(t.shape, t.primitive) for t in stq + dtq)
    d_dense = sum(data_count(t.shape, "GEMM") for t in stq + dtq)
    memory_time = d_load * hw.bytes_per_elem / hw.mem_bw

    return ScheduleReport(
        makespan=max(compute_makespan, memory_time),
        t_sparse_busy=sparse_busy,
        t_dense_busy=dense_busy,
        n_stq=len(stq),
        n_dtq=len(dtq),
        n_spdmm=sum(1 for t in stq if t.primitive == "SpDMM"),
        n_spmm=sum(1 for t in stq if t.primitive == "SpMM"),
        flops_executed=f_exec,
        flops_dense_equiv=f_dense,
        data_loaded=d_load,
        data_dense_equiv=d_dense,
        memory_time=memory_time,
    )


def simulate_sharded(
    stq: list[Task],
    dtq: list[Task],
    placement,
    hws: list[HardwareModel],
) -> ScheduleReport:
    """Simulate a device-placed plan: each device runs its band's queues
    concurrently with every other device.  Combined makespan is the slowest
    device; busy times / flops / data are totals; ``per_device`` carries the
    per-device sub-reports for :attr:`EngineReport.by_device`."""
    if placement.n_devices != len(hws):
        raise ValueError(f"placement has {placement.n_devices} devices, "
                         f"got {len(hws)} hardware models")
    per_dev = []
    for d, hw in enumerate(hws):
        per_dev.append(simulate([t for t in stq if t.device == d],
                                [t for t in dtq if t.device == d], hw))
    combined = ScheduleReport.zero()
    for rep in per_dev:
        combined = combined.merge(rep)
    return dataclasses.replace(
        combined,
        makespan=max((r.makespan for r in per_dev), default=0.0),
        per_device=tuple(per_dev),
    )
