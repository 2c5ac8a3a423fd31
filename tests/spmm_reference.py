"""Exact references of the lowered routes (a helper, not a test file).

The compiled dispatch lowers a plan's dense queue as one batched GEMM, its
SpDMM tasks as one fused entry list, and its SpMM tasks as a stripe walk:
one grid step per (stored A block, task column stripe), in the SpDMM entry
format (``repro.core.dispatch``).  :func:`stripe_walk_reference` rebuilds
that result from unfused kernels: a per-task ``ops.gemm`` for each dense
tile and :func:`section_reference` for each sparse section, the SpMM tasks
on the dense operand with its sub-eps blocks zeroed.  Against an SpMM that
intersects Y's block structure it agrees only within float32 rounding: one
(B, B)@(B, SN) dot rounds like ``SN / B`` dots of width B only up to about
one ulp.

A section whose tasks cover every column tile of each row stripe walks whole
padded rows (``repro.core.dispatch.walks_whole_rows``);
:func:`section_reference` is either walk's exact result from the unfused
kernel.

The activation route (dense X, ``ActivationDispatch``) walks column stripes
in its SpDMM section and pairs each stored block of X with every 8x8 block
of Y in its SpMM section; :func:`activation_reference` is its exact result.
:func:`pertask_reference` runs one unfused launch per task.
"""
import numpy as np

from repro.core.dispatch import canvas_slots, walks_whole_rows
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


def pertask_reference(part, stq, dtq, xd, yd, *, eps: float = 0.0,
                      block: int = 8) -> np.ndarray:
    """Each task on its own unfused launch: ``ops.gemm`` for a dense-queue
    tile, ``ops.spdmm`` for an SpDMM task and ``ops.spmm`` (X's stored
    blocks paired with Y's) for an SpMM task, both on the task's row stripe
    packed with ``eps``.  Tiles no task covers are zero."""
    xd, yd = np.asarray(xd), np.asarray(yd)
    tm, tn = part.tile_m, part.tile_n
    z = np.zeros((part.M, part.N), np.float32)

    def operands(t):
        return xd[t.i * tm:(t.i + 1) * tm], yd[:, t.j * tn:(t.j + 1) * tn]

    def put(t, tile):
        z[t.i * tm:t.i * tm + part.row_extent(t.i),
          t.j * tn:t.j * tn + part.col_extent(t.j)] = np.asarray(tile)

    for t in dtq:
        xs, ys = operands(t)
        put(t, ops.gemm(xs, ys, bm=min(128, -(-xs.shape[0] // 8) * 8),
                        interpret=True, out_dtype=np.float32))
    for t in stq:
        xs, ys = operands(t)
        a = pack_blockcsr(xs, block, eps=eps)
        if t.primitive == "SpMM":
            put(t, ops.spmm(a, pack_blockcsr(ys, block, eps=eps),
                            interpret=True))
        else:
            put(t, ops.spdmm(a, ys, bn=min(128, -(-ys.shape[1] // 8) * 8),
                             interpret=True))
    return z


def stripe_walk_reference(plan, xd, yd, *, eps: float = 0.0,
                          block: int = 8) -> np.ndarray:
    """The compiled dispatch's result for ``plan``, from unfused kernels:
    the dense queue one ``ops.gemm`` per tile, the SpDMM tasks and the SpMM
    tasks each as the section the dispatch lowers (whole rows where
    :func:`walks_whole_rows` says so), the SpMM section on the eps-masked
    Y.  ``xd`` is the dense operand (the densified adjacency)."""
    return _route_reference(plan, xd, yd, eps=eps, block=block,
                            activation=False)


def activation_reference(plan, xd, yd, *, eps: float = 0.0,
                         block: int = 8) -> np.ndarray:
    """The activation route's result for ``plan`` on the activation ``xd``,
    from unfused kernels: the dense queue one ``ops.gemm`` per tile, the
    SpDMM tasks as a column-stripe walk, and each SpMM task pairing its
    stored blocks with Y's (``ops.spmm``)."""
    return _route_reference(plan, xd, yd, eps=eps, block=block,
                            activation=True)


def _route_reference(plan, xd, yd, *, eps, block, activation):
    part = plan.part
    SM, SN = canvas_slots(part, block)
    sp = [t for t in plan.stq if t.primitive != "SpMM"]
    mm = [t for t in plan.stq if t.primitive == "SpMM"]
    # the dense tiles, and the activation route's SpMM pairs, task by task
    z = pertask_reference(part, mm if activation else [], plan.dtq, xd, yd,
                          eps=eps, block=block)
    sections = [(sp, yd)]
    if not activation:
        sections.append((mm, eps_masked(yd, eps, block)))
    for tasks, y in sections:
        if not tasks:
            continue
        whole = not activation and walks_whole_rows(
            tasks, part.n_col_tiles, SN, block)
        canvas = section_reference(part, tasks, xd, y, whole_rows=whole,
                                   eps=eps, block=block)
        for t in tasks:
            mi, nj = part.row_extent(t.i), part.col_extent(t.j)
            z[t.i * part.tile_m:t.i * part.tile_m + mi,
              t.j * part.tile_n:t.j * part.tile_n + nj] = \
                canvas[t.i * SM:t.i * SM + mi, t.j * SN:t.j * SN + nj]
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
    y_f = np.zeros((part.K, nct * SN), np.float32)
    for j in range(nct):
        w = part.col_extent(j)
        y_f[:, j * SN:j * SN + w] = yd[:, j * tn:j * tn + w]
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
