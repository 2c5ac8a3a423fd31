"""Dense GEMM Pallas kernel — the MXU analogue of the paper's AIE array.

The AIE computation core streams row-major X / column-major Y partitions and
multiply-accumulates partial products across cycles (Fig. 3).  The TPU-native
equivalent is a three-level tiled matmul: grid ``(M/bm, N/bn, K/bk)`` with the
contraction dimension innermost so the output block stays resident in VMEM
while partial products accumulate (``@pl.when(k == 0)`` zero-init mirrors the
first-cycle load in Fig. 3).  Block shapes are MXU-aligned (multiples of 128 on
the minor dims) and sized so ``bm*bk + bk*bn + bm*bn`` floats fit VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gemm_kernel(x_ref, y_ref, z_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)

    @pl.when(k == n_k - 1)
    def _store():
        z_ref[...] = acc_ref[...].astype(z_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "out_dtype")
)
def gemm(
    x: jax.Array,
    y: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """``x @ y`` with explicit MXU tiling.  Shapes must be block-divisible
    (the public wrapper in ``ops.py`` pads)."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (x.shape, y.shape, bm, bn, bk)
    out_dtype = out_dtype or x.dtype
    n_k = k // bk

    return pl.pallas_call(
        functools.partial(_gemm_kernel, n_k=n_k),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="gemm",
    )(x, y)


def _gemm_batch_scatter_kernel(row_ref, col_ref, x_ref, y_ref, zin_ref, z_ref,
                               acc_ref, *, n_k: int):
    del row_ref, col_ref, zin_ref
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[0], y_ref[0], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)

    @pl.when(k == n_k - 1)
    def _store():
        z_ref[...] = acc_ref[...].astype(z_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bk", "interpret")
)
def gemm_batch_scatter(
    x: jax.Array,
    y: jax.Array,
    rows: jax.Array,
    cols: jax.Array,
    z: jax.Array,
    *,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Batched tile GEMM with an in-place scatter output map.

    Like :func:`gemm_batch`, but instead of returning a ``(T, m, n)`` stack
    the output index map places task ``t``'s tile directly at tile
    coordinates ``(rows[t], cols[t])`` of the caller's canvas ``z`` — the
    final padded ``(M, N)`` layout of the plan's partition.  ``z`` is aliased
    to the output, so tiles owned by other primitives (or by no task) keep
    whatever ``z`` already holds; the scheduler's assembly is one slice
    instead of a per-task ``.at[].set`` loop.  ``z`` dims must be multiples
    of the tile dims ``(m, n)``.
    """
    t, m, k = x.shape
    t2, k2, n = y.shape
    assert t == t2 and k == k2, (x.shape, y.shape)
    assert k % bk == 0, (k, bk)
    mz, nz = z.shape
    assert mz % m == 0 and nz % n == 0, (z.shape, (m, n))
    n_k = k // bk

    return pl.pallas_call(
        functools.partial(_gemm_batch_scatter_kernel, n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(t, n_k),
            in_specs=[
                pl.BlockSpec((1, m, bk), lambda i, kk, rows, cols: (i, 0, kk)),
                pl.BlockSpec((1, bk, n), lambda i, kk, rows, cols: (i, kk, 0)),
                # canvas input, aliased to the output buffer: the kernel
                # never reads it, so it stays in HBM (no per-step DMA)
                pl.BlockSpec(memory_space=pltpu.HBM),
            ],
            out_specs=pl.BlockSpec(
                (m, n), lambda i, kk, rows, cols: (rows[i], cols[i])
            ),
            scratch_shapes=[pltpu.VMEM((m, n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(z.shape, z.dtype),
        input_output_aliases={4: 0},    # 2 scalar-prefetch + x + y -> z
        interpret=interpret,
        name="gemm_batch_scatter",
    )(rows, cols, x, y, z)


def _gemm_batch_kernel(x_ref, y_ref, z_ref, acc_ref, *, n_k: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[0], y_ref[0], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)

    @pl.when(k == n_k - 1)
    def _store():
        z_ref[0] = acc_ref[...].astype(z_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bk", "interpret", "out_dtype")
)
def gemm_batch(
    x: jax.Array,
    y: jax.Array,
    *,
    bk: int = 128,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Batched tile GEMM: ``z[t] = x[t] @ y[t]`` in ONE pallas_call.

    ``x`` is ``(T, m, k)`` (the stacked DTQ row-stripes), ``y`` is
    ``(T, k, n)`` (the matching col-stripes).  The grid is ``(T, k/bk)`` with
    the contraction innermost, so each task's output tile stays VMEM-resident
    while its partial products accumulate — the whole Dense Task Queue drains
    with a single kernel launch instead of one launch per task.
    """
    t, m, k = x.shape
    t2, k2, n = y.shape
    assert t == t2 and k == k2, (x.shape, y.shape)
    assert k % bk == 0, (k, bk)
    out_dtype = out_dtype or x.dtype
    n_k = k // bk

    return pl.pallas_call(
        functools.partial(_gemm_batch_kernel, n_k=n_k),
        grid=(t, n_k),
        in_specs=[
            pl.BlockSpec((1, m, bk), lambda i, kk: (i, 0, kk)),
            pl.BlockSpec((1, bk, n), lambda i, kk: (i, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, m, n), lambda i, kk: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((m, n), jnp.float32)],
        interpret=interpret,
        name="gemm_batch",
    )(x, y)
