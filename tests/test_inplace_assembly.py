"""In-place fused output assembly (tentpole of ISSUE 3).

The fused kernels' output index maps scatter every task's tile directly
into the final padded ``(M, N)`` canvas of the plan's partition, chained
across primitives via output aliasing — a compiled dispatch assembles with
ONE slice, no per-task ``.at[].set`` scatter.  These tests pin the
load-bearing properties: bit-identical results vs the exact unfused
references (all three primitives, ragged edge tiles), zero-retention for
tiles no task covers, and the refusal of misaligned geometry.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import DynasparseEngine, SparseCOO
from repro.core import dispatch as dispatch_mod
from repro.core.partition import make_tasks
from repro.core.plancache import StructureEntry
from repro.core import sparsity
from repro.kernels import ops
from spmm_reference import (pertask_reference, section_reference,
                            stripe_walk_reference)

RNG = np.random.default_rng(17)


def _run(part, stq, dtq, xd, yd):
    """The compiled dispatch of a (hand-built) assignment, run once."""
    stripes = {i: ops.pack_blockcsr(np.asarray(xd)[i * part.tile_m:
                                                   (i + 1) * part.tile_m], 8)
               for i in {t.i for t in stq}}
    d = dispatch_mod.build_dispatch(part, stq, dtq, stripes, block=8)
    return np.asarray(dispatch_mod.apply_dispatch(
        d.geom, d.arrays, jnp.asarray(xd) if dtq else None,
        jnp.asarray(yd), interpret=True))


def _mixed_ragged_plan():
    """A plan with all three primitives AND ragged edge tiles:
    M=90 over tile_m=32 (extents 32/32/26), N=44 over tile_n=24 (24/20)."""
    rng = np.random.default_rng(1)
    xd = rng.normal(size=(90, 64)).astype(np.float32)
    xd[:32] *= (rng.uniform(size=(32, 64)) < 0.01)
    xd[32:64] *= (rng.uniform(size=(32, 64)) < 0.3)
    yd = rng.normal(size=(64, 44)).astype(np.float32)
    yd[:, :24] *= (rng.uniform(size=(64, 24)) < 0.05)
    r, c = np.nonzero(xd)
    x = SparseCOO(xd.shape, jnp.asarray(r.astype(np.int32)),
                  jnp.asarray(c.astype(np.int32)),
                  jnp.asarray(xd[r, c]), tag="adjacency")
    eng = DynasparseEngine(tile_m=32, tile_n=24, literal=True)
    plan = eng.plan(x, jnp.asarray(yd))
    return plan, xd, yd


def test_inplace_mixed_primitives_ragged_bitwise():
    """In-place assembly == its exact unfused reference, bit for bit, on a
    plan mixing GEMM/SpDMM/SpMM with ragged row and column edge tiles; the
    per-task reference within float32 rounding (its SpMM pairs 8x8 Y
    blocks where the dispatch walks column stripes)."""
    plan, xd, yd = _mixed_ragged_plan()
    prims = {t.primitive for t in plan.stq} | {t.primitive for t in plan.dtq}
    assert prims == {"SpDMM", "SpMM", "GEMM"}, prims

    z_b = _run(plan.part, plan.stq, plan.dtq, xd, yd)
    np.testing.assert_array_equal(z_b, stripe_walk_reference(plan, xd, yd))
    z_p = pertask_reference(plan.part, plan.stq, plan.dtq, xd, yd)
    np.testing.assert_allclose(z_b, z_p, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(z_b, xd @ yd, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("primitive", ["GEMM", "SpDMM", "SpMM"])
def test_inplace_single_primitive_ragged_bitwise(primitive):
    """Each fused kernel alone must scatter every tile — including the
    ragged edge tiles — into the right canvas region, matching its exact
    unfused reference bit for bit, in ONE launch."""
    rng = np.random.default_rng(7)
    M, K, N = 40, 32, 20            # tiles 16/8 -> extents 16/16/8, 8/8/4
    xd = (rng.normal(size=(M, K)) *
          (rng.uniform(size=(M, K)) < 0.4)).astype(np.float32)
    yd = (rng.normal(size=(K, N)) *
          (rng.uniform(size=(K, N)) < 0.5)).astype(np.float32)
    tm, tn = 16, 8
    row_d = np.asarray(sparsity.stripe_density(jnp.asarray(xd), tm, axis=0))
    col_d = np.asarray(sparsity.stripe_density(jnp.asarray(yd), tn, axis=1))
    part = make_tasks("k", M, K, N, row_d, col_d, tm, tn)
    for t in part.tasks:
        t.primitive = primitive
        t.queue = "DTQ" if primitive == "GEMM" else "STQ"
    stq = [t for t in part.tasks if t.queue == "STQ"]
    dtq = [t for t in part.tasks if t.queue == "DTQ"]

    ops.reset_pallas_call_count()
    z_b = _run(part, stq, dtq, xd, yd)
    assert ops.pallas_call_count() == 1          # ONE fused launch
    z_p = pertask_reference(part, stq, dtq, xd, yd)
    if primitive == "GEMM":
        np.testing.assert_array_equal(z_b, z_p)
    else:
        # the tasks cover every column tile, so the section walks whole
        # 24-wide rows: bitwise its unfused reference, and the per-task
        # reference's 8-wide dots within float32 rounding
        z_r = section_reference(part, stq, xd, yd, whole_rows=True)[:M, :N]
        np.testing.assert_array_equal(z_b, z_r)
        np.testing.assert_allclose(z_b, z_p, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(z_b, xd @ yd, rtol=1e-4, atol=1e-4)


def test_uncovered_tiles_stay_zero():
    """Tiles belonging to no executed task must come out exactly zero — the
    aliased canvas keeps the zero init where no output index map points."""
    plan, xd, yd = _mixed_ragged_plan()
    part = plan.part
    # drain ONLY the sparse queue: every dense-queue tile region must be 0
    z = _run(part, plan.stq, [], xd, yd)
    tm, tn = part.tile_m, part.tile_n
    for task in plan.dtq:
        mi, dj = part.row_extent(task.i), part.col_extent(task.j)
        tile = z[task.i * tm: task.i * tm + mi,
                 task.j * tn: task.j * tn + dj]
        np.testing.assert_array_equal(tile, np.zeros_like(tile))
    # and the sparse-queue tiles are untouched by the omission
    z_full = _run(part, plan.stq, plan.dtq, xd, yd)
    for task in plan.stq:
        mi, dj = part.row_extent(task.i), part.col_extent(task.j)
        np.testing.assert_array_equal(
            z[task.i * tm: task.i * tm + mi,
              task.j * tn: task.j * tn + dj],
            z_full[task.i * tm: task.i * tm + mi,
                   task.j * tn: task.j * tn + dj])


def test_misaligned_tiles_fall_back_and_match():
    """Hand-built geometry whose interior tile boundaries are not
    lcm(block, 8)-aligned cannot use the in-place index maps: lowering it
    raises ValueError, for the compiled and the activation route alike,
    and an engine refuses such tiles before it plans."""
    rng = np.random.default_rng(3)
    M, K, N = 36, 24, 16
    xd = (rng.normal(size=(M, K)) *
          (rng.uniform(size=(M, K)) < 0.3)).astype(np.float32)
    yd = rng.normal(size=(K, N)).astype(np.float32)
    tm, tn = 12, 8                   # tm = 12 is not a multiple of 8
    row_d = np.asarray(sparsity.stripe_density(jnp.asarray(xd), tm, axis=0))
    col_d = np.asarray(sparsity.stripe_density(jnp.asarray(yd), tn, axis=1))
    part = make_tasks("k", M, K, N, row_d, col_d, tm, tn)
    for t in part.tasks:             # mixed queues across the grid
        t.primitive = "SpDMM" if (t.i + t.j) % 2 else "GEMM"
        t.queue = "STQ" if t.primitive == "SpDMM" else "DTQ"
    stq = [t for t in part.tasks if t.queue == "STQ"]
    dtq = [t for t in part.tasks if t.queue == "DTQ"]

    with pytest.raises(ValueError, match="lcm"):
        _run(part, stq, dtq, xd, yd)
    with pytest.raises(ValueError, match="lcm"):
        dispatch_mod.build_activation_dispatch(part, stq, dtq, block=8,
                                               capacity=4)
    with pytest.raises(ValueError, match="tile_m=12"):
        DynasparseEngine(tile_m=tm, tile_n=tn, literal=True).plan(
            jnp.asarray(xd), jnp.asarray(yd))
    # the same queues on an aligned geometry lower and match
    row_d8 = np.asarray(sparsity.stripe_density(jnp.asarray(xd), 8, axis=0))
    part8 = make_tasks("k", M, K, N, row_d8, col_d, 8, tn)
    for t in part8.tasks:
        t.primitive = "SpDMM" if (t.i + t.j) % 2 else "GEMM"
        t.queue = "STQ" if t.primitive == "SpDMM" else "DTQ"
    stq8 = [t for t in part8.tasks if t.queue == "STQ"]
    dtq8 = [t for t in part8.tasks if t.queue == "DTQ"]
    z = _run(part8, stq8, dtq8, xd, yd)
    np.testing.assert_array_equal(z, pertask_reference(part8, stq8, dtq8,
                                                       xd, yd))
    np.testing.assert_allclose(z, xd @ yd, rtol=1e-4, atol=1e-4)


def test_misaligned_tiles_sparse_only_engine_uses_packed_fallback():
    """An engine with misaligned tile sizes is refused at planning, before
    it packs or densifies anything; with aligned tiles an all-sparse plan
    executes from the packed stripes alone (graph-scale mode: the operand
    is never densified)."""
    rng = np.random.default_rng(9)
    n, nnz = 36, 60
    flat = np.sort(rng.choice(n * n, size=nnz, replace=False))
    adj = SparseCOO((n, n),
                    jnp.asarray((flat // n).astype(np.int32)),
                    jnp.asarray((flat % n).astype(np.int32)),
                    jnp.asarray(np.abs(rng.normal(size=nnz)
                                       ).astype(np.float32)),
                    tag="adjacency")
    y = rng.normal(size=(n, 8)).astype(np.float32)
    eng = DynasparseEngine(tile_m=12, tile_n=8, literal=True,
                           mode="sparse_only")
    with pytest.raises(ValueError, match="tile_m=12"):
        eng.matmul(adj, jnp.asarray(y))
    assert len(eng.cache) == 0
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True,
                           mode="sparse_only")
    z, _ = eng.matmul(adj, jnp.asarray(y))
    assert eng.last_route[0] == "sparse" and not eng.last_route[1].needs_x
    assert all(v.dense is None for v, _ in eng.cache._entries.values()
               if isinstance(v, StructureEntry))
    np.testing.assert_allclose(np.asarray(z), adj.todense() @ y,
                               rtol=1e-4, atol=1e-4)


def test_single_stripe_padded_slots_inplace():
    """nrt == 1 / nct == 1 with tile sizes that aren't lcm-aligned still
    takes the in-place path (slot padding only ever extends past M/N)."""
    rng = np.random.default_rng(5)
    M, K, N = 20, 16, 5              # single 20x5 tile: SM=40? no — SM=ru(20,8)=24, SN=8
    xd = (rng.normal(size=(M, K)) *
          (rng.uniform(size=(M, K)) < 0.4)).astype(np.float32)
    yd = rng.normal(size=(K, N)).astype(np.float32)
    eng = DynasparseEngine(tile_m=128, tile_n=128, literal=True)
    z, _ = eng.matmul(jnp.asarray(xd), jnp.asarray(yd))
    assert z.shape == (M, N)
    np.testing.assert_allclose(np.asarray(z), xd @ yd, rtol=1e-4, atol=1e-4)
