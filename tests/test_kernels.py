"""Per-kernel allclose validation against the pure-jnp oracles.

All Pallas kernels run in interpret mode on CPU (TPU is the target).
Shapes/dtypes are swept deterministically here; the hypothesis-driven
property sweeps live in ``test_properties.py`` (guarded import — the suite
must collect without the optional dev dependency).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import formats, ops, ref
from repro.kernels.formats import pack_blockcsr, pack_blockcsr_coo

jax.config.update("jax_enable_x64", False)

RNG = np.random.default_rng(1234)


def _rand(m, n, dtype, density=1.0, block_mask=None, block=None):
    x = RNG.normal(size=(m, n)).astype(np.float32)
    if density < 1.0 and block_mask is None:
        mask = RNG.uniform(size=(m, n)) < density
        x = x * mask
    if block_mask is not None:
        bm = np.kron(block_mask, np.ones((block, block)))[:m, :n]
        x = x * bm
    return x.astype(dtype)


TOL = {np.float32: 2e-5, jnp.bfloat16: 2e-1}


# ---------------------------------------------------------------- GEMM
@pytest.mark.parametrize("m,k,n", [(8, 8, 8), (32, 16, 24), (128, 128, 128),
                                   (100, 60, 36), (256, 128, 64)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_gemm_matches_ref(m, k, n, dtype):
    x = _rand(m, k, dtype)
    y = _rand(k, n, dtype)
    got = ops.gemm(jnp.asarray(x), jnp.asarray(y), bm=32, bn=32, bk=32,
                   interpret=True, out_dtype=jnp.float32)
    want = ref.gemm_ref(jnp.asarray(x), jnp.asarray(y), out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=TOL[dtype], atol=TOL[dtype] * 10)


def test_gemm_block_shape_sweep():
    x = _rand(64, 48, np.float32)
    y = _rand(48, 80, np.float32)
    want = np.asarray(ref.gemm_ref(jnp.asarray(x), jnp.asarray(y)))
    for b in (8, 16, 64, 128):
        got = ops.gemm(jnp.asarray(x), jnp.asarray(y), bm=b, bn=b, bk=b,
                       interpret=True)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-4)


# ---------------------------------------------------------------- SpDMM
@pytest.mark.parametrize("block", [8, 16])
@pytest.mark.parametrize("density", [0.0, 0.1, 0.5, 1.0])
def test_spdmm_block_density_sweep(block, density):
    m, k, n = 4 * block, 6 * block, 3 * block
    nrb, ncb = m // block, k // block
    block_mask = (RNG.uniform(size=(nrb, ncb)) < density).astype(np.float32)
    a_dense = _rand(m, k, np.float32, block_mask=block_mask, block=block)
    y = _rand(k, n, np.float32)
    a = pack_blockcsr(a_dense, block)
    got = ops.spdmm(a, jnp.asarray(y), bn=block, interpret=True)
    want = a_dense.astype(np.float32) @ y
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-4)


def test_spdmm_ragged_shapes():
    # logical shapes not multiples of block
    block = 16
    a_dense = _rand(50, 70, np.float32, density=0.2)
    y = _rand(70, 36, np.float32)
    a = pack_blockcsr(a_dense, block)
    got = ops.spdmm(a, jnp.asarray(y), bn=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), a_dense @ y, rtol=2e-5,
                               atol=2e-4)


def test_spdmm_capacity_padding_is_noop():
    block = 8
    a_dense = _rand(32, 32, np.float32, density=0.3)
    y = _rand(32, 16, np.float32)
    a0 = pack_blockcsr(a_dense, block)
    a1 = pack_blockcsr(a_dense, block, capacity=a0.stored_blocks + 7)
    g0 = ops.spdmm(a0, jnp.asarray(y), bn=8, interpret=True)
    g1 = ops.spdmm(a1, jnp.asarray(y), bn=8, interpret=True)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1), atol=1e-6)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_spdmm_dtypes(dtype):
    block = 8
    a_dense = _rand(24, 40, dtype, density=0.4)
    y = _rand(40, 24, dtype)
    a = pack_blockcsr(a_dense, block)
    got = ops.spdmm(a, jnp.asarray(y), bn=8, interpret=True)
    want = np.asarray(a_dense, np.float32) @ np.asarray(y, np.float32)
    np.testing.assert_allclose(np.asarray(got), want, rtol=TOL[dtype],
                               atol=TOL[dtype] * 10)


# The TPU interpreter starts every VMEM buffer as NaN, as the chip leaves it
# uninitialized: a launch that failed to resume a split run reads NaN.
TPU_INTERPRET = pltpu.InterpretParams(uninitialized_memory="nan")


def _fused_spdmm_operands(n_stripes, bn, block=8):
    """One fused SpDMM entry list: every stored block of a random sparse A
    against ``n_stripes`` column stripes of Y, sorted by output block."""
    a_dense = _rand(5 * block, 6 * block, np.float32, density=0.3)
    y = _rand(6 * block, n_stripes * bn, np.float32)
    a = pack_blockcsr(a_dense, block)
    nb = a.nnzb
    rows = np.asarray(a.row_ids)[:nb]
    cols = np.asarray(a.col_ids)[:nb]
    first = np.asarray(a.first)[:nb]
    entries = sorted((int(r), j, i) for j in range(n_stripes)
                     for i, r in enumerate(rows))
    e = np.array(entries, dtype=np.int32)
    ids = (e[:, 2], cols[e[:, 2]], e[:, 0], e[:, 1], first[e[:, 2]])
    return a, a_dense, y, ids


@pytest.mark.parametrize("n_stripes,bn", [(1, 16), (3, 8)])
@pytest.mark.parametrize("max_entries", [1, 3, 7])
def test_spdmm_fused_split_launches_bitwise(n_stripes, bn, max_entries,
                                            monkeypatch):
    """An entry list split across launches (the per-launch SMEM limit) gives
    the single launch's bits: runs cut by a launch boundary resume from the
    canvas.  Column stripes narrower than 128 lanes (bn=8 over a 24-wide Y)
    exercise the stripe-major layout."""
    a, a_dense, y, ids = _fused_spdmm_operands(n_stripes, bn)
    m_pad = a.n_block_rows * 8
    canvas = jnp.full((m_pad, n_stripes * bn), 7.0, jnp.float32)
    kw = dict(block_size=8, bn=bn, m_pad=m_pad, interpret=TPU_INTERPRET)
    one = ops.spdmm_fused(a.blocks, jnp.asarray(y), *ids, z=canvas, **kw)
    monkeypatch.setattr(ops, "MAX_ENTRIES_PER_LAUNCH", max_entries)
    launches0 = ops.pallas_call_count()
    split = ops.spdmm_fused(a.blocks, jnp.asarray(y), *ids, z=canvas, **kw)
    assert ops.pallas_call_count() - launches0 == -(-len(ids[0])
                                                    // max_entries)
    np.testing.assert_array_equal(np.asarray(split), np.asarray(one))
    np.testing.assert_allclose(np.asarray(one)[:a_dense.shape[0]],
                               a_dense @ y, rtol=2e-5, atol=2e-4)
    fresh = ops.spdmm_fused(a.blocks, jnp.asarray(y), *ids, **kw)
    np.testing.assert_array_equal(np.asarray(fresh), np.asarray(one))


# ---------------------------------------------------------------- SpMM
@pytest.mark.parametrize("da,dy", [(0.0, 0.5), (0.2, 0.2), (0.5, 1.0),
                                   (1.0, 1.0), (1.0, 0.0)])
def test_spmm_density_sweep(da, dy):
    block = 8
    m, k, n = 3 * block, 4 * block, 2 * block
    am = (RNG.uniform(size=(m // block, k // block)) < da).astype(np.float32)
    ym = (RNG.uniform(size=(k // block, n // block)) < dy).astype(np.float32)
    a_dense = _rand(m, k, np.float32, block_mask=am, block=block)
    y_dense = _rand(k, n, np.float32, block_mask=ym, block=block)
    a = pack_blockcsr(a_dense, block)
    y = pack_blockcsr(y_dense, block)
    got = ops.spmm(a, y, interpret=True)
    np.testing.assert_allclose(np.asarray(got), a_dense @ y_dense,
                               rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("max_entries", [1, 5])
def test_spmm_fused_split_launches_bitwise(max_entries, monkeypatch):
    """The triple list split across launches matches one launch bitwise,
    over a Y of several block columns (block-column-major output)."""
    block = 8
    a_dense = _rand(4 * block, 5 * block, np.float32, density=0.3)
    y_dense = _rand(5 * block, 3 * block, np.float32, density=0.3)
    a = pack_blockcsr(a_dense, block)
    y = pack_blockcsr(y_dense, block)
    triples = formats.spmm_triples(a, y)
    a_blocks = jnp.concatenate([a.blocks, jnp.zeros((1, 8, 8))], axis=0)
    y_blocks = jnp.concatenate([y.blocks, jnp.zeros((1, 8, 8))], axis=0)
    kw = dict(block_size=block, m_pad=4 * block, n_pad=3 * block,
              interpret=TPU_INTERPRET)
    one = ops.spmm_fused(a_blocks, y_blocks, *triples, **kw)
    monkeypatch.setattr(ops, "MAX_ENTRIES_PER_LAUNCH", max_entries)
    split = ops.spmm_fused(a_blocks, y_blocks, *triples, **kw)
    np.testing.assert_array_equal(np.asarray(split), np.asarray(one))
    np.testing.assert_allclose(np.asarray(one), a_dense @ y_dense,
                               rtol=2e-5, atol=2e-4)


def test_spmm_ragged():
    block = 8
    a_dense = _rand(20, 28, np.float32, density=0.3)
    y_dense = _rand(28, 12, np.float32, density=0.3)
    a = pack_blockcsr(a_dense, block)
    y = pack_blockcsr(y_dense, block)
    got = ops.spmm(a, y, interpret=True)
    np.testing.assert_allclose(np.asarray(got), a_dense @ y_dense,
                               rtol=2e-5, atol=2e-4)


def test_blockcsr_roundtrip():
    a_dense = _rand(40, 24, np.float32, density=0.25)
    a = pack_blockcsr(a_dense, 8)
    np.testing.assert_allclose(np.asarray(a.todense()), a_dense, atol=0)


# ------------------------------------------------- COO packing (no densify)
def _assert_blockcsr_identical(a, b):
    assert a.shape == b.shape and a.block_size == b.block_size
    assert a.nnzb == b.nnzb
    np.testing.assert_array_equal(np.asarray(a.row_ids), np.asarray(b.row_ids))
    np.testing.assert_array_equal(np.asarray(a.col_ids), np.asarray(b.col_ids))
    np.testing.assert_array_equal(np.asarray(a.first), np.asarray(b.first))
    # bit-identical blocks, not allclose: COO packing must sum duplicates in
    # triplet order exactly like np.add.at on the densified matrix
    np.testing.assert_array_equal(np.asarray(a.blocks), np.asarray(b.blocks))


@pytest.mark.parametrize("m,k,eps", [(40, 24, 0.0), (37, 21, 0.0),
                                     (64, 64, 1e-6)])
def test_pack_blockcsr_coo_bit_identical_to_dense_path(m, k, eps):
    dense = _rand(m, k, np.float32, density=0.15)
    if eps > 0:   # sprinkle sub-eps values that must not resurrect a block
        dense[dense == 0] = np.where(
            RNG.uniform(size=(dense == 0).sum()) < 0.2, 1e-9, 0.0
        ).astype(np.float32)
    r, c = np.nonzero(dense)
    got = pack_blockcsr_coo((m, k), r.astype(np.int32), c.astype(np.int32),
                            dense[r, c], 8, eps=eps)
    want = pack_blockcsr(dense, 8, eps=eps)
    _assert_blockcsr_identical(got, want)


def test_pack_blockcsr_coo_duplicates_sum_in_order():
    # duplicate coordinates: the dense oracle accumulates with np.add.at in
    # triplet order; the COO pack must produce the same float32 bit pattern
    rows = np.array([0, 0, 5, 0, 5], dtype=np.int32)
    cols = np.array([1, 1, 3, 1, 3], dtype=np.int32)
    vals = np.array([0.1, 0.7, -0.3, 1e-8, 0.30000001], dtype=np.float32)
    dense = np.zeros((8, 8), np.float32)
    np.add.at(dense, (rows, cols), vals)
    got = pack_blockcsr_coo((8, 8), rows, cols, vals, 4)
    want = pack_blockcsr(dense, 4)
    _assert_blockcsr_identical(got, want)


def test_pack_blockcsr_coo_rejects_out_of_bounds():
    for bad_r, bad_c in [(-1, 0), (16, 0), (0, -2), (0, 8)]:
        with pytest.raises(ValueError, match="out of bounds"):
            pack_blockcsr_coo((16, 8), np.array([bad_r], np.int32),
                              np.array([bad_c], np.int32),
                              np.ones(1, np.float32), 8)


def test_pack_blockcsr_coo_empty_and_capacity():
    got = pack_blockcsr_coo((16, 8), np.zeros(0, np.int32),
                            np.zeros(0, np.int32), np.zeros(0, np.float32),
                            8, capacity=4)
    want = pack_blockcsr(np.zeros((16, 8), np.float32), 8, capacity=4)
    _assert_blockcsr_identical(got, want)
    assert got.stored_blocks == 4 and got.nnzb == 2  # one zero block per row
