"""SpDMM Pallas kernel — block-sparse x dense (the PL ALU-array analogue).

Paper Alg. 2 pairs every nonzero element of X with q dense lanes of Y via the
Pairing Unit.  TPU-native version: the sparse operand is ``BlockCSR`` and the
grid iterates *only the stored blocks*; scalar-prefetched ``row_ids/col_ids``
arrays play the role of the Pairing Unit, steering each stored A-block to the
matching Y block-row and output block-row.  Work (and hence cycles) scales
with the number of stored blocks — i.e. with block density α_blk — exactly the
paper's ``α · mnd`` skip behaviour at tile granularity.

Grid order is ``(N/bn, nnzb)`` with the block index innermost: for a fixed
output column stripe, stored blocks are visited sorted by block-row, so output
block revisits are consecutive and the accumulator stays VMEM-resident
(TPU requirement); ``first`` flags zero-initialize each output row run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.formats import BlockCSR


def _spdmm_kernel(row_ref, col_ref, first_ref, a_ref, y_ref, z_ref):
    del col_ref
    b = pl.program_id(1)

    @pl.when(first_ref[b] == 1)
    def _init():
        z_ref[...] = jnp.zeros_like(z_ref)

    # BlockSpec (None, B, B) squeezes the stored-block axis: a_ref is (B, B)
    z_ref[...] += jnp.dot(
        a_ref[...], y_ref[...], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST).astype(z_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "interpret", "out_dtype"))
def spdmm(
    a: BlockCSR,
    y: jax.Array,
    *,
    bn: int = 128,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """``a @ y`` where ``a`` is BlockCSR and ``y`` dense ``(K, N)``.

    ``K`` and ``N`` must be multiples of ``a.block_size`` / ``bn``
    (the wrapper in ``ops.py`` pads).  Output is dense ``(M, N)`` where
    ``M = n_block_rows * block_size`` (caller slices).
    """
    B = a.block_size
    k, n = y.shape
    assert k == a.n_block_cols * B, (a.shape, y.shape, B)
    assert n % bn == 0, (n, bn)
    m_pad = a.n_block_rows * B
    nnzb = a.blocks.shape[0]

    grid = (n // bn, nnzb)
    return pl.pallas_call(
        _spdmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                # stored A blocks: one (B, B) block per inner step
                pl.BlockSpec((None, B, B), lambda j, b, rows, cols, first: (b, 0, 0)),
                # Y block-row selected by the block's column id (Pairing Unit)
                pl.BlockSpec((B, bn), lambda j, b, rows, cols, first: (cols[b], j)),
            ],
            out_specs=pl.BlockSpec(
                (B, bn), lambda j, b, rows, cols, first: (rows[b], j)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), out_dtype),
        interpret=interpret,
        name="spdmm",
    )(a.row_ids, a.col_ids, a.first, a.blocks, y)


def _spdmm_fused_kernel(aid_ref, yrow_ref, orow_ref, ocol_ref, first_ref,
                        a_ref, y_ref, z_ref):
    del aid_ref, yrow_ref, orow_ref, ocol_ref
    t = pl.program_id(0)
    z = z_ref.at[0]               # (B, bn) view of the (1, B, bn) block

    @pl.when(first_ref[t] == 1)
    def _init():
        z[...] = jnp.zeros_like(z)

    z[...] += jnp.dot(
        a_ref[...], y_ref[...], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST).astype(z.dtype)


def resume_partial(first_ref, zin_ref, z_ref):
    """Open a launch in the middle of an output block's run: when the first
    entry of this launch continues a run (``first == 0``), the partial sum
    the previous launch of the same entry list wrote into the aliased
    canvas (``zin_ref``, fetched block by block alongside the output) is
    loaded into the resident output block before accumulating.  Long entry
    lists are split across launches (one launch's scalar-prefetch operands
    must fit in SMEM), and this keeps the split invisible: the float32
    partial round-trips through HBM unchanged, so the result is
    bit-identical to one launch."""
    @pl.when((pl.program_id(0) == 0) & (first_ref[0] == 0))
    def _resume():
        z_ref[...] = zin_ref[...]


def _spdmm_fused_inplace_kernel(aid_ref, yrow_ref, orow_ref, ocol_ref,
                                first_ref, a_ref, y_ref, zin_ref, z_ref):
    resume_partial(first_ref, zin_ref, z_ref)
    _spdmm_fused_kernel(aid_ref, yrow_ref, orow_ref, ocol_ref, first_ref,
                        a_ref, y_ref, z_ref)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "m_pad", "interpret", "out_dtype",
                     "n_entries", "name"),
)
def spdmm_fused(
    a_blocks: jax.Array,
    y: jax.Array,
    a_ids: jax.Array,
    y_rows: jax.Array,
    out_rows: jax.Array,
    out_cols: jax.Array,
    first: jax.Array,
    *,
    block_size: int,
    m_pad: int,
    interpret: bool = False,
    out_dtype=jnp.float32,
    n_entries: int,
    z: jax.Array | None = None,
    name: str = "spdmm_fused",
) -> jax.Array:
    """Fused multi-task SpDMM: EVERY SpDMM task of a kernel in one launch.

    ``a_blocks`` is the concatenated stored-block pool of all packed row
    stripes.  The dense operand and the output are STRIPE-MAJOR: ``y`` is
    ``(n_stripes, k_pad, bn)`` and the output ``(n_stripes, m_pad, bn)``,
    one slab per ``bn``-wide column stripe, so every block a grid step
    touches spans the full minor dimension of its array (the TPU block
    rule: the last two block dims are multiples of (8, 128) or equal to the
    array's).  Each grid step ``t`` is one (stored block, task) pair: the
    scalar-prefetched entry arrays steer block ``a_ids[t]`` onto Y block-row
    ``y_rows[t]`` of stripe ``out_cols[t]`` and accumulate into output
    block ``(out_rows[t], out_cols[t])``.  Entries are sorted by output block
    so revisits are consecutive (VMEM residency); ``first`` zero-initializes
    each run.

    Without ``z``, the output is a fresh buffer whose blocks covered by no
    entry are undefined (the caller must not read them).  With ``z`` — the
    scheduler's in-place assembly — the canvas is aliased to the output, so
    covered blocks are written in place and every other block keeps its
    ``z`` content (e.g. tiles already written by the batched GEMM of the
    same kernel), and the launch may open mid-run (:func:`resume_partial`).
    ``name`` is the launch's name in a profile.
    """
    B = block_size
    n_stripes, k_pad, bn = y.shape
    assert k_pad % B == 0, (y.shape, B)

    in_specs = [
        pl.BlockSpec((None, B, B),
                     lambda t, aid, yrow, orow, ocol, first: (aid[t], 0, 0)),
        pl.BlockSpec((None, B, bn),
                     lambda t, aid, yrow, orow, ocol, first:
                     (ocol[t], yrow[t], 0)),
    ]
    out_spec = pl.BlockSpec(
        (1, B, bn),
        lambda t, aid, yrow, orow, ocol, first: (ocol[t], orow[t], 0))
    operands = [a_ids, y_rows, out_rows, out_cols, first, a_blocks, y]
    kernel = _spdmm_fused_kernel
    out_shape = jax.ShapeDtypeStruct((n_stripes, m_pad, bn), out_dtype)
    aliases = {}
    if z is not None:
        assert z.shape == (n_stripes, m_pad, bn), (z.shape, m_pad, y.shape)
        # canvas input, aliased to the output buffer: fetched once per
        # output block (the pipeline skips unchanged block indices) and
        # read only to resume a split run
        in_specs.append(out_spec)
        operands.append(z)
        kernel = _spdmm_fused_inplace_kernel
        out_shape = jax.ShapeDtypeStruct(z.shape, z.dtype)
        aliases = {7: 0}            # 5 scalar-prefetch + a + y -> z

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_entries,),
            in_specs=in_specs,
            out_specs=out_spec,
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name=name,
    )(*operands)
