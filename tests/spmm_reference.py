"""Exact reference of the compiled SpMM section (a helper, not a test file).

The compiled dispatch lowers a plan's SpMM tasks as a stripe walk: one grid
step per (stored A block, task column stripe), in the SpDMM entry format
(``repro.core.dispatch``).  Its result is bit-identical to the eager batched
run of the same tasks relabelled SpDMM — the same ``spdmm_fused``, entry
order and stripe width — on the dense operand with its sub-eps blocks
zeroed.  Against the eager SpMM, which intersects Y's block structure, it
agrees only within float32 rounding: one (B, B)@(B, SN) dot rounds like
``SN / B`` dots of width B only up to about one ulp.
"""
import dataclasses

import numpy as np

from repro.core.dispatch import canvas_slots
from repro.core.scheduler import execute_plan
from repro.kernels.formats import block_nonzero_mask


def eps_masked(y, eps: float, block: int = 8) -> np.ndarray:
    """``y`` with every ``block`` x ``block`` block whose magnitudes are all
    ``<= eps`` zeroed — what an eps-thresholded pack drops."""
    y = np.asarray(y)
    if eps == 0.0:
        return y
    k, n = y.shape
    kp, n_p = -(-k // block) * block, -(-n // block) * block
    yp = np.zeros((kp, n_p), y.dtype)
    yp[:k, :n] = y
    yb = yp.reshape(kp // block, block, n_p // block, block)
    keep = block_nonzero_mask(yb, eps, axis=(1, 3))
    return np.where(keep[:, None, :, None], yb,
                    np.zeros((), y.dtype)).reshape(kp, n_p)[:k, :n]


def stripe_walk_reference(plan, xd, yd, *, eps: float = 0.0, block: int = 8,
                          **kw) -> np.ndarray:
    """The compiled dispatch's result for ``plan``, computed eagerly: the
    GEMM and SpDMM tasks as planned, the SpMM tasks relabelled SpDMM on the
    eps-masked Y.  A geometry the compiled dispatch declines runs the
    eager path, and so does its reference.  ``kw`` goes to
    :func:`execute_plan`."""
    part = plan.part
    if canvas_slots(part, block) is None:
        return np.asarray(execute_plan(part, plan.stq, plan.dtq, xd, yd,
                                       block=block, batched=True, eps=eps,
                                       **kw))
    spmm = [t for t in plan.stq if t.primitive == "SpMM"]
    rest = [t for t in plan.stq if t.primitive != "SpMM"]
    z = np.array(execute_plan(part, rest, plan.dtq, xd, yd, block=block,
                              batched=True, eps=eps, **kw))
    if spmm:
        z_mm = np.asarray(execute_plan(
            part, [dataclasses.replace(t, primitive="SpDMM") for t in spmm],
            [], xd, eps_masked(yd, eps, block), block=block, batched=True,
            eps=eps, **kw))
        for t in spmm:
            rows = slice(t.i * part.tile_m,
                         t.i * part.tile_m + part.row_extent(t.i))
            cols = slice(t.j * part.tile_n,
                         t.j * part.tile_n + part.col_extent(t.j))
            z[rows, cols] = z_mm[rows, cols]
    return z
