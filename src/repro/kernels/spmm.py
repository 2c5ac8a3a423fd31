"""SpMM Pallas kernel — block-sparse x block-sparse (paper Alg. 3).

Row-wise product: ``Z[jb] = Σ_ib A[jb, ib] · Y[ib]`` computed only over pairs
where BOTH blocks are stored.  The host-side ``spmm_triples`` pairing (the
paper's Pairing Unit intersecting X's row nonzeros with Y's stored rows)
produces a flat triple list sorted by output block; the grid walks that list,
so compute scales with ``α_blk(A) · α_blk(Y)`` — the paper's
``α_X · α_Y · mnd`` term at tile granularity.

Each grid step multiplies one stored-A block into one stored-Y block and
accumulates into the output block addressed by the scalar-prefetched
``out_rows/out_cols``; sorting makes revisits consecutive (VMEM residency) and
``first`` flags zero-initialize.  A sentinel zero block appended after the
stored blocks backs the padding triples that cover otherwise-empty output
blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.formats import BlockCSR, spmm_triples
from repro.kernels.spdmm import resume_partial


def _spmm_kernel(aid_ref, yid_ref, orow_ref, ocol_ref, first_ref,
                 a_ref, y_ref, z_ref):
    del aid_ref, yid_ref, orow_ref, ocol_ref
    t = pl.program_id(0)
    z = z_ref.at[0]               # (B, B) view of the (1, B, B) block

    @pl.when(first_ref[t] == 1)
    def _init():
        z[...] = jnp.zeros_like(z)

    # BlockSpec (None, B, B) squeezes the stored-block axis: refs are (B, B)
    z[...] += jnp.dot(
        a_ref[...], y_ref[...], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST).astype(z.dtype)


def _spmm_inplace_kernel(aid_ref, yid_ref, orow_ref, ocol_ref, first_ref,
                         a_ref, y_ref, zin_ref, z_ref):
    resume_partial(first_ref, zin_ref, z_ref)
    _spmm_kernel(aid_ref, yid_ref, orow_ref, ocol_ref, first_ref,
                 a_ref, y_ref, z_ref)


@functools.partial(
    jax.jit,
    static_argnames=("m_pad", "n_pad", "block_size", "interpret", "out_dtype",
                     "n_triples"),
)
def spmm_fused(a_blocks, y_blocks, a_ids, y_ids, out_rows, out_cols, first,
               *, m_pad, n_pad, block_size, interpret=False,
               out_dtype=jnp.float32, n_triples, z=None):
    """One launch of the triple-walking kernel over CONCATENATED block pools
    (all packed A row-stripes / Y col-stripes of a kernel, plus one trailing
    sentinel zero block each).  The caller offsets block ids into the pools
    and output coordinates into per-task regions; sorting/coverage
    obligations are those of :func:`repro.kernels.formats.spmm_triples`.

    The output is BLOCK-COLUMN-MAJOR, ``(n_pad // B, m_pad, B)``: each
    (B, B) output block then spans the full minor dimension of its array,
    which the TPU block rule requires of a B-wide block (B < 128).  ``z``
    (optional) is an in-place canvas in that layout, aliased to the output:
    triples scatter into it, every block they don't cover keeps its ``z``
    content, and the launch may open mid-run (``resume_partial``)."""
    B = block_size
    in_specs = [
        pl.BlockSpec((None, B, B), lambda t, aid, yid, orow, ocol, first: (aid[t], 0, 0)),
        pl.BlockSpec((None, B, B), lambda t, aid, yid, orow, ocol, first: (yid[t], 0, 0)),
    ]
    out_spec = pl.BlockSpec(
        (1, B, B), lambda t, aid, yid, orow, ocol, first: (ocol[t], orow[t], 0))
    operands = [a_ids, y_ids, out_rows, out_cols, first, a_blocks, y_blocks]
    kernel = _spmm_kernel
    out_shape = jax.ShapeDtypeStruct((n_pad // B, m_pad, B), out_dtype)
    aliases = {}
    if z is not None:
        assert z.shape == (n_pad // B, m_pad, B), (z.shape, m_pad, n_pad)
        # canvas input, aliased to the output buffer: fetched once per
        # output block and read only to resume a split run
        in_specs.append(out_spec)
        operands.append(z)
        kernel = _spmm_inplace_kernel
        out_shape = jax.ShapeDtypeStruct(z.shape, z.dtype)
        aliases = {7: 0}            # 5 scalar-prefetch + a + y -> z

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_triples,),
            in_specs=in_specs,
            out_specs=out_spec,
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name="spmm_fused",
    )(*operands)


def spmm(
    a: BlockCSR,
    y: BlockCSR,
    *,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """``a @ y`` with both operands BlockCSR.  Returns dense
    ``(n_block_rows(a)*B, n_block_cols(y)*B)`` (caller slices to logical)."""
    B = a.block_size
    a_ids, y_ids, out_rows, out_cols, first = spmm_triples(a, y)

    # sentinel zero blocks backing the padding triples
    zero = jnp.zeros((1, B, B), a.blocks.dtype)
    a_blocks = jnp.concatenate([a.blocks, zero], axis=0)
    zero_y = jnp.zeros((1, B, B), y.blocks.dtype)
    y_blocks = jnp.concatenate([y.blocks, zero_y], axis=0)

    m_pad, n_pad = a.n_block_rows * B, y.n_block_cols * B
    out = spmm_fused(
        a_blocks, y_blocks,
        jnp.asarray(a_ids), jnp.asarray(y_ids),
        jnp.asarray(out_rows), jnp.asarray(out_cols), jnp.asarray(first),
        m_pad=m_pad, n_pad=n_pad, block_size=B, interpret=interpret,
        out_dtype=out_dtype, n_triples=len(a_ids))
    return out.transpose(1, 0, 2).reshape(m_pad, n_pad)
