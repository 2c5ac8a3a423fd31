"""Operations and bytes of a model's kernels, fixed by their shapes.

Whatever implements a kernel, its operations are those of the model:

- an aggregation ``Â · X`` counts ``2 · nnz(Â) · width(X)``;
- a transform ``X · W`` counts ``2 · rows · d_in · d_out``, dense;
- elementwise work is not counted.

Bytes are each operand read once and the output written once, float32:
a dense operand at its full size, ``Â`` as compressed rows (a value and a
column index per stored entry, one offset per row).  A kernel's least time
is the larger of its operations over the peak rate and its bytes over the
peak bandwidth.
"""
from __future__ import annotations

import dataclasses

F32 = 4


@dataclasses.dataclass(frozen=True)
class Kernel:
    name: str
    kind: str        # "agg": Â (m x k, nnz stored) · X (k x n); "dense": X · W
    m: int
    k: int
    n: int
    nnz: int = 0

    @property
    def ops(self) -> float:
        if self.kind == "agg":
            return 2.0 * self.nnz * self.n
        return 2.0 * self.m * self.k * self.n

    @property
    def bytes(self) -> float:
        if self.kind == "agg":
            a = self.nnz * 2 * F32 + (self.m + 1) * F32
        else:
            a = self.m * self.k * F32
        return a + (self.k * self.n + self.m * self.n) * F32

    def least_s(self, peak: dict) -> float:
        return max(self.ops / peak["flops_per_s_bf16"],
                   self.bytes / peak["hbm_bytes_per_s"])


def agg(name: str, n: int, nnz: int, width: int) -> Kernel:
    return Kernel(name, "agg", n, n, width, nnz)


def dense(name: str, rows: int, d_in: int, d_out: int) -> Kernel:
    return Kernel(name, "dense", rows, d_in, d_out)


def model_ops(kernels) -> float:
    return sum(k.ops for k in kernels)


def least_s(kernels, peak: dict) -> float:
    return sum(k.least_s(peak) for k in kernels)
