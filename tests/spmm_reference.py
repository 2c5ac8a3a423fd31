"""Exact references of the compiled sparse sections (a helper, not a test
file).

The compiled dispatch lowers a plan's SpMM tasks as a stripe walk: one grid
step per (stored A block, task column stripe), in the SpDMM entry format
(``repro.core.dispatch``).  Its result is bit-identical to the eager batched
run of the same tasks relabelled SpDMM — the same ``spdmm_fused``, entry
order and stripe width — on the dense operand with its sub-eps blocks
zeroed.  Against the eager SpMM, which intersects Y's block structure, it
agrees only within float32 rounding: one (B, B)@(B, SN) dot rounds like
``SN / B`` dots of width B only up to about one ulp.

A section whose tasks cover every column tile of each row stripe walks whole
padded rows (``repro.core.dispatch.walks_whole_rows``), the eager batched
SpDMM section too; :func:`section_reference` is either walk's exact result
from the unfused kernel.
"""
import dataclasses

import numpy as np

from repro.core.dispatch import canvas_slots
from repro.core.scheduler import execute_plan
from repro.kernels import ops
from repro.kernels.formats import block_nonzero_mask, pack_blockcsr


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


def section_reference(part, tasks, xd, yd, *, whole_rows: bool,
                      eps: float = 0.0, block: int = 8) -> np.ndarray:
    """Exact reference of one sparse section in the SpDMM entry format, from
    the unfused ``ops.spdmm``: each row stripe of ``xd`` packed (with
    ``eps``) times the stripe-padded ``yd``, in dots as wide as the walk —
    the whole padded row once per stripe (``whole_rows``), else one column
    stripe per task.  The same products in the same A-block order and dot
    width as the fused walk, so equal to it bit for bit.  Returns the
    padded canvas; regions no task covers are zero."""
    B = block
    SM, SN = canvas_slots(part, B)
    nct, tm, tn = part.n_col_tiles, part.tile_m, part.tile_n
    ncb = -(-part.K // B)
    y_f = np.zeros((ncb * B, nct * SN), np.float32)
    for j in range(nct):
        w = part.col_extent(j)
        y_f[:part.K, j * SN:j * SN + w] = yd[:, j * tn:j * tn + w]
    z = np.zeros((part.n_row_tiles * SM, nct * SN), np.float32)
    units = ({(t.i, None) for t in tasks} if whole_rows
             else {(t.i, t.j) for t in tasks})
    for i, j in sorted(units, key=lambda u: (u[0], u[1] or 0)):
        a = pack_blockcsr(np.asarray(xd)[i * tm:(i + 1) * tm], B, eps=eps)
        cols = slice(None) if j is None else slice(j * SN, (j + 1) * SN)
        out = np.asarray(ops.spdmm(a, y_f[:, cols], bn=y_f[:, cols].shape[1],
                                   interpret=True))
        z[i * SM:i * SM + out.shape[0], cols] = out
    return z
