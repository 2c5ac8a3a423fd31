"""Public jit'd wrappers around the Pallas kernels.

Handles shape padding to block multiples, operand layouts the TPU block rule
accepts, splitting of long fused entry lists across launches (SMEM), dtype
policy (f32 accumulation) and interpret mode: the kernels target TPU, and on
any other backend ``interpret=True`` executes the kernel body on the host for
validation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import gemm as _gemm
from repro.kernels import spdmm as _spdmm
from repro.kernels import spmm as _spmm
from repro.kernels.formats import (BlockCSR, block_nonzero_mask,
                                   pack_blockcsr)


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# Pallas-invocation accounting: every public wrapper bumps this counter, so
# the scheduler tests/benchmarks can assert batched dispatch really issues
# O(primitives) launches per kernel instead of O(tasks).
_PALLAS_CALLS = 0


def _count_call() -> None:
    global _PALLAS_CALLS
    _PALLAS_CALLS += 1


def pallas_call_count() -> int:
    return _PALLAS_CALLS


def reset_pallas_call_count() -> None:
    global _PALLAS_CALLS
    _PALLAS_CALLS = 0


def _pad_to(x: jax.Array, m: int, n: int) -> jax.Array:
    pm = m - x.shape[0]
    pn = n - x.shape[1]
    if pm == 0 and pn == 0:
        return x
    return jnp.pad(x, ((0, pm), (0, pn)))


def _round_up(x: int, b: int) -> int:
    return -(-x // b) * b


def gemm(x, y, *, bm: int = 128, bn: int = 128, bk: int = 128,
         interpret: bool | None = None, out_dtype=None):
    """Dense ``x @ y`` via the MXU-tiled Pallas kernel (pads + slices)."""
    interpret = default_interpret() if interpret is None else interpret
    m, k = x.shape
    k2, n = y.shape
    assert k == k2
    bm_, bn_, bk_ = (min(bm, _round_up(m, 8)), min(bn, _round_up(n, 8)),
                     min(bk, _round_up(k, 8)))
    mp, np_, kp = _round_up(m, bm_), _round_up(n, bn_), _round_up(k, bk_)
    _count_call()
    out = _gemm.gemm(_pad_to(x, mp, kp), _pad_to(y, kp, np_),
                     bm=bm_, bn=bn_, bk=bk_, interpret=interpret,
                     out_dtype=out_dtype)
    return out[:m, :n]


def gemm_batch(x, y, *, bk: int = 128, interpret: bool | None = None,
               out_dtype=jnp.float32):
    """Batched tile GEMM ``z[t] = x[t] @ y[t]`` in one pallas_call.

    ``x`` is ``(T, m, k)``, ``y`` is ``(T, k, n)``; tile dims are padded to
    lane multiples and the output sliced back to ``(T, m, n)``."""
    interpret = default_interpret() if interpret is None else interpret
    t, m, k = x.shape
    t2, k2, n = y.shape
    assert t == t2 and k == k2, (x.shape, y.shape)
    bk_ = min(bk, _round_up(k, 8))
    mp, np_, kp = _round_up(m, 8), _round_up(n, 8), _round_up(k, bk_)
    x = jnp.pad(x, ((0, 0), (0, mp - m), (0, kp - k)))
    y = jnp.pad(y, ((0, 0), (0, kp - k), (0, np_ - n)))
    _count_call()
    out = _gemm.gemm_batch(x, y, bk=bk_, interpret=interpret,
                           out_dtype=out_dtype)
    return out[:, :m, :n]


def gemm_batch_scatter(x, y, rows, cols, z, *, bk: int = 128,
                       interpret: bool | None = None):
    """Batched tile GEMM scattered in place: ``z`` at tile coords
    ``(rows[t], cols[t])`` receives ``x[t] @ y[t]`` — one pallas_call, no
    host-side reassembly.  ``x`` is ``(T, m, k)``, ``y`` is ``(T, k, n)``
    and ``z``'s dims must be multiples of ``(m, n)`` (the scheduler's padded
    canvas guarantees this); tiles of ``z`` no task addresses keep their
    content (aliased output)."""
    interpret = default_interpret() if interpret is None else interpret
    t, m, k = x.shape
    t2, k2, n = y.shape
    assert t == t2 and k == k2, (x.shape, y.shape)
    bk_ = min(bk, _round_up(k, 8))
    kp = _round_up(k, bk_)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, kp - k)))
    y = jnp.pad(y, ((0, 0), (0, kp - k), (0, 0)))
    _count_call()
    return _gemm.gemm_batch_scatter(
        x, y, jnp.asarray(rows, dtype=jnp.int32),
        jnp.asarray(cols, dtype=jnp.int32), z, bk=bk_, interpret=interpret)


def spdmm(a: BlockCSR, y, *, bn: int = 128, interpret: bool | None = None,
          out_dtype=jnp.float32):
    """Block-sparse ``a @ y`` (pads Y, slices output to logical shape)."""
    interpret = default_interpret() if interpret is None else interpret
    m, k = a.shape
    k2, n = y.shape
    assert k == k2, (a.shape, y.shape)
    bn_ = min(bn, _round_up(n, 8))
    kp = a.n_block_cols * a.block_size
    np_ = _round_up(n, bn_)
    _count_call()
    out = _spdmm.spdmm(a, _pad_to(y, kp, np_), bn=bn_, interpret=interpret,
                       out_dtype=out_dtype)
    return out[:m, :n]


# One launch's scalar-prefetch operands live in SMEM, 1 MiB on TPU v5e (the
# compiler refuses more).  The fused sparse kernels prefetch five int32
# descriptor arrays, 20 bytes per entry, so about 52k entries is the most a
# single launch can take; longer entry lists run as consecutive launches of
# this many entries on the same aliased canvas (bit-identical to one launch,
# see ``repro.kernels.spdmm.resume_partial``).
MAX_ENTRIES_PER_LAUNCH = 32768

# A fused sparse launch whose ``bn`` spans a whole padded output row holds
# six (B, bn) blocks in VMEM: the Y block-row, the output block and the
# aliased canvas block, each double-buffered.  Capping one float32 block at
# 512 KiB (16,384 columns at B = 8) keeps the six near 3 MiB, well inside a
# v5e core's 16 MiB of scoped VMEM; wider rows walk one column stripe at a
# time.
MAX_ROW_BLOCK_BYTES = 512 * 1024


def _launch_chunks(n: int):
    """``[lo, hi)`` entry ranges of the launches a fused list of ``n``
    entries is split into."""
    step = MAX_ENTRIES_PER_LAUNCH
    return [(lo, min(n, lo + step)) for lo in range(0, max(n, 1), step)]


def _stripe_major(z, n_stripes: int, width: int):
    """``(rows, n_stripes * width)`` → ``(n_stripes, rows, width)``."""
    return z.reshape(z.shape[0], n_stripes, width).transpose(1, 0, 2)


def _row_major(z3):
    """Inverse of :func:`_stripe_major`."""
    n_stripes, rows, width = z3.shape
    return z3.transpose(1, 0, 2).reshape(rows, n_stripes * width)


def spdmm_fused(a_blocks, y, a_ids, y_rows, out_rows, out_cols, first, *,
                block_size: int, bn: int, m_pad: int,
                interpret: bool | None = None, out_dtype=jnp.float32,
                z=None, name: str = "spdmm_fused"):
    """Fused multi-task SpDMM over a concatenated stored-block pool; see
    :func:`repro.kernels.spdmm.spdmm_fused`.  ``y`` must already be laid out
    with ``bn``-padded col-stripes; it and the output are handled stripe-
    major inside.  ``z`` (optional) is an in-place canvas aliased to the
    output: uncovered blocks keep their ``z`` content.  Entry lists longer
    than :data:`MAX_ENTRIES_PER_LAUNCH` run as several launches on one
    canvas."""
    interpret = default_interpret() if interpret is None else interpret
    k_pad, n_pad = y.shape
    assert n_pad % bn == 0, (y.shape, bn)
    n_stripes = n_pad // bn
    ids = [jnp.asarray(v, dtype=jnp.int32)
           for v in (a_ids, y_rows, out_rows, out_cols, first)]
    n = len(a_ids)
    z3 = None if z is None else _stripe_major(jnp.asarray(z), n_stripes, bn)
    if z3 is None and n > MAX_ENTRIES_PER_LAUNCH:
        z3 = jnp.zeros((n_stripes, m_pad, bn), out_dtype)
    y3 = _stripe_major(jnp.asarray(y), n_stripes, bn)
    a_blocks = jnp.asarray(a_blocks)
    for lo, hi in _launch_chunks(n):
        _count_call()
        z3 = _spdmm.spdmm_fused(
            a_blocks, y3, *(v[lo:hi] for v in ids),
            block_size=block_size, m_pad=m_pad, interpret=interpret,
            out_dtype=out_dtype, n_entries=hi - lo, z=z3, name=name)
    return _row_major(z3)


def blockize(y, block: int):
    """Dense ``(R*B, C*B)`` matrix → ``(R*C, B, B)`` block pool in row-major
    block order (``pool[r*C + c] == y[r*B:(r+1)*B, c*B:(c+1)*B]``).

    The activation route's SpMM derives its Y operand pool from the dense
    matrix at run time (a reshape/transpose, no host packing), addressed by
    ``y_id = row_block * C + col_block`` descriptors."""
    m, n = y.shape
    assert m % block == 0 and n % block == 0, (y.shape, block)
    r, c = m // block, n // block
    return y.reshape(r, block, c, block).transpose(0, 2, 1, 3).reshape(
        r * c, block, block)


def pack_activation_stripes(x, *, block: int, n_stripes: int, slot_rows: int,
                            n_block_cols: int, capacity,
                            eps: float = 0.0):
    """Traceable capacity-padded BlockCSR packing of a dense activation.

    The device-resident analogue of per-row-stripe :func:`pack_blockcsr` —
    runs INSIDE a jitted program (no host round-trip), with **fixed shapes**
    so one trace serves any activation sparsity within the stored-block
    budget.  ``x`` is the dense ``(M, K)`` operand; ``capacity`` is either a
    static int (every stripe gets the same budget) or a static per-stripe
    vector of ``n_stripes`` ints (skew-aware budgets — stripes packed back
    to back at flat offsets ``cumsum(capacity)``, so the trace shape depends
    only on the TOTAL slot count).  Each of the ``n_stripes`` canvas
    row-stripes (``slot_rows`` block-rows tall) is packed into exactly its
    budgeted number of block slots:

    - stored blocks (any ``|elem| > eps``; ``!= 0`` when ``eps == 0``) fill
      slots in row-major (block-row, block-col) order — the same order
      ``pack_blockcsr`` emits;
    - block-rows with no stored block keep one zero block at column 0 with
      ``first = 1`` (output-init coverage), including the canvas padding
      rows past the logical extent;
    - remaining slots are the capacity-padding convention: zero block at
      the LAST block-row, column 0, ``first = 0`` — exact bitwise no-ops.

    Returns ``(blocks, row_ids, col_ids, first, nnzb, real, overflow)``:
    the pooled ``(sum(capacity), B, B)`` slot payloads, the flat per-slot
    metadata (int32, indexable by ``offset[stripe] + slot`` — with a scalar
    capacity that is the familiar ``stripe * capacity + slot``), the
    per-stripe SLOT counts (stored blocks + empty-row fillers — what the
    budget must cover), the per-stripe count of REAL stored blocks (fillers
    excluded — the honest skip telemetry), and a scalar bool that is True
    when ANY stripe needs more than its budgeted slots (blocks past the
    budget are dropped — the caller must take its dense fallback).
    """
    B, S, R, C = block, n_stripes, slot_rows, n_block_cols
    caps = np.asarray(capacity, dtype=np.int64)
    if caps.ndim == 0:
        caps = np.full(S, int(caps), dtype=np.int64)
    assert caps.shape == (S,), (caps.shape, S)
    offs = np.concatenate([np.zeros(1, np.int64), np.cumsum(caps)])
    total = int(offs[-1])
    x = jnp.asarray(x)
    M, K = x.shape
    xp = jnp.pad(x, ((0, S * R * B - M), (0, C * B - K)))
    xb = xp.reshape(S, R, B, C, B).transpose(0, 1, 3, 2, 4)   # (S,R,C,B,B)
    mask = block_nonzero_mask(xb, eps, axis=(-2, -1), xp=jnp)
    row_has = jnp.any(mask, axis=2)                           # (S, R)
    col0 = jax.lax.broadcasted_iota(jnp.int32, (S, R, C), 2) == 0
    stored = mask | ((~row_has)[:, :, None] & col0)
    first = stored & (jnp.cumsum(stored.astype(jnp.int32), axis=2) == 1)

    flat = stored.reshape(S, R * C)
    cnt = jnp.cumsum(flat.astype(jnp.int32), axis=1)
    slot = cnt - 1
    nnzb = cnt[:, -1]
    # filler/padding slots carry EXACT zero blocks (jnp.where, not a mask
    # multiply: ``-x * 0 == -0.0`` would leak signed zeros into the pool)
    blocks = jnp.where(mask[..., None, None], xb,
                       jnp.zeros((), x.dtype)).reshape(S, R * C, B, B)
    r_idx = jax.lax.broadcasted_iota(jnp.int32, (S, R, C), 1).reshape(S, R * C)
    c_idx = jax.lax.broadcasted_iota(jnp.int32, (S, R, C), 2).reshape(S, R * C)
    # scatter each stored block to its flat slot ``offset[stripe] + slot``;
    # non-stored and over-budget blocks target slot == total, which 'drop'
    # discards.  With a scalar capacity the offsets are ``stripe * cap`` and
    # the layout is bit-identical to the historical 2-D (S, cap) scatter.
    caps_j = jnp.asarray(caps, jnp.int32)[:, None]        # (S, 1), static
    offs_j = jnp.asarray(offs[:-1], jnp.int32)[:, None]
    tgt = jnp.where(flat & (slot < caps_j), offs_j + slot, total).reshape(-1)
    pool = jnp.zeros((total, B, B), x.dtype
                     ).at[tgt].set(blocks.reshape(S * R * C, B, B),
                                   mode="drop")
    row_ids = jnp.full((total,), R - 1, jnp.int32
                       ).at[tgt].set(r_idx.reshape(-1), mode="drop")
    col_ids = jnp.zeros((total,), jnp.int32
                        ).at[tgt].set(c_idx.reshape(-1), mode="drop")
    first_f = jnp.zeros((total,), jnp.int32).at[tgt].set(
        first.reshape(-1).astype(jnp.int32), mode="drop")
    return (pool, row_ids, col_ids, first_f, nnzb,
            jnp.sum(mask.astype(jnp.int32), axis=(1, 2)),
            jnp.any(nnzb > jnp.asarray(caps, jnp.int32)))


def spmm(a: BlockCSR, y: BlockCSR, *, interpret: bool | None = None,
         out_dtype=jnp.float32):
    """Block-sparse ``a @ y`` with both operands sparse."""
    interpret = default_interpret() if interpret is None else interpret
    m, _ = a.shape
    _, n = y.shape
    _count_call()
    out = _spmm.spmm(a, y, interpret=interpret, out_dtype=out_dtype)
    return out[:m, :n]


def spmm_fused(a_blocks, y_blocks, a_ids, y_ids, out_rows, out_cols, first, *,
               block_size: int, m_pad: int, n_pad: int,
               interpret: bool | None = None, out_dtype=jnp.float32, z=None):
    """Fused multi-task SpMM over concatenated block pools; see
    :func:`repro.kernels.spmm.spmm_fused`.  ``z`` (optional) is an in-place
    canvas aliased to the output: uncovered blocks keep their ``z`` content.
    Triple lists longer than :data:`MAX_ENTRIES_PER_LAUNCH` run as several
    launches on one canvas."""
    interpret = default_interpret() if interpret is None else interpret
    B = block_size
    ids = [jnp.asarray(v, dtype=jnp.int32)
           for v in (a_ids, y_ids, out_rows, out_cols, first)]
    n = len(a_ids)
    z3 = None if z is None else _stripe_major(jnp.asarray(z), n_pad // B, B)
    if z3 is None and n > MAX_ENTRIES_PER_LAUNCH:
        z3 = jnp.zeros((n_pad // B, m_pad, B), out_dtype)
    a_blocks, y_blocks = jnp.asarray(a_blocks), jnp.asarray(y_blocks)
    for lo, hi in _launch_chunks(n):
        _count_call()
        z3 = _spmm.spmm_fused(
            a_blocks, y_blocks, *(v[lo:hi] for v in ids),
            block_size=B, m_pad=m_pad, n_pad=n_pad, interpret=interpret,
            out_dtype=out_dtype, n_triples=hi - lo, z=z3)
    return _row_major(z3)


__all__ = [
    "BlockCSR", "pack_blockcsr", "pack_activation_stripes", "blockize",
    "gemm", "gemm_batch", "gemm_batch_scatter",
    "spdmm", "spdmm_fused", "spmm", "spmm_fused", "default_interpret",
    "pallas_call_count", "reset_pallas_call_count", "MAX_ENTRIES_PER_LAUNCH",
    "MAX_ROW_BLOCK_BYTES",
]
