"""Compiled dispatch (tentpole of ISSUE 4).

A planned kernel is lowered ONCE into a device-resident CompiledDispatch
(sorted descriptor arrays + pooled blocks, vectorized numpy build) and every
later execute is a single jitted call.  These tests pin the load-bearing
properties: bit-identity against the exact unfused reference of the lowering
(``spmm_reference``: GEMM and SpDMM as planned, SpMM tasks as the SpDMM
stripe walk) and agreement with the per-task reference (whose SpMM
intersects Y's structure) across ragged/mixed-primitive geometries, the
stripe walk's grid-step count, zero host descriptor work in steady state,
honest cache accounting/eviction, the refusal of misaligned geometry, and
the whole-model compiler.
"""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import DynasparseEngine, SparseCOO
from repro.core import dispatch as dispatch_mod
from repro.core.partition import choose_tile, make_tasks
from repro.core.plancache import PlanCache
from repro.data import graphs
from repro.data.graphs import load_graph
from repro.kernels.formats import pack_blockcsr_coo
from repro.models import gnn
from spmm_reference import (eps_masked, pertask_reference,
                            stripe_walk_reference)

RNG = np.random.default_rng(31)


def _coo_of(xd: np.ndarray) -> SparseCOO:
    r, c = np.nonzero(xd)
    return SparseCOO(xd.shape, jnp.asarray(r.astype(np.int32)),
                     jnp.asarray(c.astype(np.int32)),
                     jnp.asarray(xd[r, c]), tag="adjacency")


def _mixed_ragged_operands(seed=1, M=90, K=64, N=44):
    """Sparsity bands that land tasks in all three primitives, with ragged
    row and column edge tiles under (tile_m=32, tile_n=24)."""
    rng = np.random.default_rng(seed)
    xd = rng.normal(size=(M, K)).astype(np.float32)
    xd[:32] *= (rng.uniform(size=(32, K)) < 0.01)
    xd[32:64] *= (rng.uniform(size=(32, K)) < 0.3)
    yd = rng.normal(size=(K, N)).astype(np.float32)
    yd[:, :24] *= (rng.uniform(size=(K, 24)) < 0.05)
    return xd, yd


def _all_paths(eng, xd, yd):
    """(compiled, exact reference, per-task reference) results of one
    planned kernel."""
    x = _coo_of(xd)
    plan = eng.plan(x, jnp.asarray(yd))
    z_c = eng.execute(plan, x, jnp.asarray(yd))
    z_r = stripe_walk_reference(plan, xd, yd)
    z_p = pertask_reference(plan.part, plan.stq, plan.dtq, xd, yd)
    return plan, np.asarray(z_c), z_r, z_p


def _assert_matches(z_c, z_r, z_p):
    """Bitwise the exact reference; the structure-intersecting per-task
    reference within float32 rounding."""
    np.testing.assert_array_equal(z_c, z_r)
    np.testing.assert_allclose(z_c, z_p, rtol=1e-4, atol=1e-4)


def test_compiled_mixed_primitives_ragged_bitwise():
    xd, yd = _mixed_ragged_operands()
    eng = DynasparseEngine(tile_m=32, tile_n=24, literal=True)
    plan, z_c, z_r, z_p = _all_paths(eng, xd, yd)
    prims = {t.primitive for t in plan.stq} | {t.primitive for t in plan.dtq}
    assert prims == {"SpDMM", "SpMM", "GEMM"}, prims
    assert eng.cache.stats.dispatch_builds == 1   # compiled path was taken
    _assert_matches(z_c, z_r, z_p)
    np.testing.assert_allclose(z_c, xd @ yd, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("tm,tn,mkn,seed", [
    (16, 8, (40, 32, 20), 7),     # ragged both axes
    (32, 8, (64, 48, 8), 3),      # single col stripe
    (8, 16, (24, 16, 33), 11),    # ragged col tail
    (128, 128, (20, 16, 5), 5),   # single padded slot
])
def test_compiled_bit_identity_across_geometries(tm, tn, mkn, seed):
    M, K, N = mkn
    rng = np.random.default_rng(seed)
    xd = (rng.normal(size=(M, K)) *
          (rng.uniform(size=(M, K)) < 0.3)).astype(np.float32)
    yd = (rng.normal(size=(K, N)) *
          (rng.uniform(size=(K, N)) < 0.5)).astype(np.float32)
    eng = DynasparseEngine(tile_m=tm, tile_n=tn, literal=True)
    _, z_c, z_r, z_p = _all_paths(eng, xd, yd)
    _assert_matches(z_c, z_r, z_p)
    np.testing.assert_allclose(z_c, xd @ yd, rtol=1e-4, atol=1e-4)


def test_steady_state_builds_nothing_and_hits_trace():
    """Second execute of the same plan: descriptor build count frozen, the
    dispatch is a cache hit, the jit trace is a hit, result identical."""
    xd, yd = _mixed_ragged_operands(seed=2)
    x = _coo_of(xd)
    eng = DynasparseEngine(tile_m=32, tile_n=24, literal=True)
    z1, _ = eng.matmul(x, jnp.asarray(yd))
    s = eng.cache.stats
    builds = s.dispatch_builds
    assert builds == 1

    # any attempt to lower descriptors again (or run per-block Python
    # loops) in steady state is the regression this PR removes
    def _boom(*a, **k):
        raise AssertionError("descriptor build ran on a plan-cache hit")
    orig = dispatch_mod.build_dispatch
    dispatch_mod.build_dispatch = _boom
    try:
        z2, _ = eng.matmul(x, jnp.asarray(yd))
    finally:
        dispatch_mod.build_dispatch = orig
    assert s.dispatch_builds == builds
    assert s.dispatch_hits >= 1
    assert s.trace_cache_hits >= 1
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))


@pytest.mark.parametrize("eps", [1e-7, 0.2])
def test_eps_spmm_compiles_bit_identically(eps):
    """Regression (ISSUE 5): eps != 0 with SpMM tasks used to DECLINE
    compilation and silently stay eager.  The eps mask (sub-eps Y blocks
    zeroed inside the traced program) lifts the gate: such plans compile,
    and the result is bit-identical to the exact reference on the
    eps-masked Y and agrees with the per-task reference under the same
    eps."""
    xd, yd = _mixed_ragged_operands(seed=4)
    x = _coo_of(xd)
    eng = DynasparseEngine(tile_m=32, tile_n=24, literal=True, eps=eps)
    plan = eng.plan(x, jnp.asarray(yd))
    if not any(t.primitive == "SpMM" for t in plan.stq):
        pytest.skip("plan routed no SpMM tasks")
    assert eng.dispatch_for(plan, x) is not None
    z_c = eng.execute(plan, x, jnp.asarray(yd))
    assert eng.cache.stats.dispatch_builds == 1
    _assert_matches(np.asarray(z_c),
                    stripe_walk_reference(plan, xd, yd, eps=eps),
                    pertask_reference(plan.part, plan.stq, plan.dtq, xd, yd,
                                      eps=eps))
    if eps <= 1e-6:     # tolerance below the operands' magnitude floor:
        np.testing.assert_allclose(np.asarray(z_c), xd @ yd,   # == dense
                                   rtol=1e-4, atol=1e-4)


def _all_spmm(plan):
    """``plan`` with every task sent to the sparse queue as SpMM."""
    return dataclasses.replace(
        plan, dtq=[],
        stq=[dataclasses.replace(t, primitive="SpMM", queue="STQ")
             for t in plan.stq + plan.dtq])


def test_spmm_stripe_walk_steps_at_co_l1_agg_geometry():
    """GIN's ``l1-agg`` on CO: the adjacency times eight stacked requests of
    1,433 features (N = 11,464) in 384 x 1536 tiles, every task on SpMM.
    The tasks cover every column tile of every row stripe, so the compiled
    section walks whole rows: one grid step per stored block, 5,499, each
    over the 12,288-wide padded row.  With one task moved to the dense
    queue its row stripe is no longer covered and the section walks one
    step per (stored block, task column stripe), as the 5,499 x 8 walk did
    less that task's stripe; the 8-wide pairing took one per (stored block,
    8x8 Y block), 5,499 x 1,433.  Descriptors only: no kernel runs."""
    g = load_graph("CO")
    M = g.adj.shape[0]
    N = 8 * g.stats.features
    tm, tn = choose_tile(M, N)
    assert (M, N, tm, tn) == (2708, 11464, 384, 1536)
    part = make_tasks("l1-agg", M, M, N, np.ones(-(-M // tm)),
                      np.ones(-(-N // tn)), tm, tn)
    stq = [dataclasses.replace(t, primitive="SpMM", queue="STQ")
           for t in part.tasks]
    rows, cols, vals = (np.asarray(v)
                        for v in (g.adj.rows, g.adj.cols, g.adj.vals))
    stripes = {}
    for i in range(part.n_row_tiles):
        sel = (rows >= i * tm) & (rows < (i + 1) * tm)
        stripes[i] = pack_blockcsr_coo((part.row_extent(i), M),
                                       rows[sel] - i * tm, cols[sel],
                                       vals[sel], 8)
    d = dispatch_mod.build_dispatch(part, stq, [], stripes, block=8)
    nnzb = sum(s.nnzb for s in stripes.values())
    assert nnzb == 5499
    assert d.geom.has_spmm and not d.geom.has_spdmm
    assert d.geom.mm_rows and not d.geom.sp_rows
    assert d.row_walk_sections == 1
    assert d.n_spmm_steps == d.sparse_steps == nnzb == 5_499
    assert set(np.asarray(d.arrays["mm_out_cols"])) == {0}

    moved = stq[3]
    assert (moved.i, moved.j) == (0, 3)
    d_split = dispatch_mod.build_dispatch(
        part, [t for t in stq if t is not moved],
        [dataclasses.replace(moved, primitive="GEMM", queue="DTQ")],
        stripes, block=8)
    assert not d_split.geom.mm_rows and d_split.row_walk_sections == 0
    per_stripe = nnzb * part.n_col_tiles - stripes[moved.i].nnzb
    assert d_split.n_spmm_steps == per_stripe == 43_992 - 746
    y_block_cols = sum(-(-part.col_extent(j) // 8)
                       for j in range(part.n_col_tiles))
    assert nnzb * y_block_cols == 7_880_067      # the 8-wide pairing's steps


def test_row_walk_steps_at_fl_l1_agg_geometry():
    """SAGE's ``l1-agg`` on FL: the adjacency of Flickr's Table IV size (the
    benchmark's ``sage-fl`` graph: 89,250 vertices, 899,756 edges, graph
    seed 1) times eight stacked requests of hidden width 128, in 11,264 x
    128 tiles, every task on SpDMM.  The whole-row walk takes one grid step
    per stored block, 900,699, where one step per (stored block, column
    tile) took 7,205,592.  Descriptors only: no pool, no kernel."""
    rng = np.random.default_rng(1)
    M = 89250
    adj = graphs._normalize_adj(M, *graphs._gen_edges(rng, M, 899756))
    N = 8 * 128
    tm, tn = choose_tile(M, N)
    assert (tm, tn) == (11264, 128)
    part = make_tasks("l1-agg", M, M, N, np.ones(-(-M // tm)),
                      np.ones(-(-N // tn)), tm, tn)
    assert (part.n_row_tiles, part.n_col_tiles) == (8, 8)
    tasks = [dataclasses.replace(t, primitive="SpDMM", queue="STQ")
             for t in part.tasks]
    rows, cols, vals = (np.asarray(v)
                        for v in (adj.rows, adj.cols, adj.vals))
    starts = np.searchsorted(rows, np.arange(part.n_row_tiles + 1) * tm)
    stripes, offsets, off = {}, {}, 0
    for i in range(part.n_row_tiles):
        lo, hi = starts[i], starts[i + 1]
        stripes[i] = pack_blockcsr_coo((part.row_extent(i), M),
                                       rows[lo:hi] - i * tm, cols[lo:hi],
                                       vals[lo:hi], 8)
        offsets[i], off = off, off + stripes[i].nnzb
    assert off == 900_699
    assert dispatch_mod.walks_whole_rows(tasks, part.n_col_tiles, tn, 8)
    walks = {whole: dispatch_mod.spdmm_entry_arrays(
        tasks, stripes, offsets, tm // 8, whole_rows=whole)
        for whole in (True, False)}
    assert len(walks[True][0]) == 900_699
    assert len(walks[False][0]) == 7_205_592
    # the same stored blocks, once per row rather than once per stripe
    np.testing.assert_array_equal(
        np.sort(walks[True][0]), np.arange(900_699))
    np.testing.assert_array_equal(
        np.bincount(walks[False][0], minlength=900_699), 8)


def test_eps_mask_drops_sub_eps_y_blocks_in_stripe_walk():
    """eps != 0 on stripes 32 wide, every task SpMM: Y blocks whose
    magnitudes are all <= eps are zeroed before the stripe walk, as the
    eager pack drops them.  Bitwise the exact reference on the masked Y,
    the per-task SpMM within float32 rounding, and measurably not the
    product with the unmasked Y."""
    eps = 0.2
    rng = np.random.default_rng(12)
    M, K, N = 48, 40, 64
    xd = (rng.normal(size=(M, K)) *
          (rng.uniform(size=(M, K)) < 0.3)).astype(np.float32)
    yd = rng.normal(size=(K, N)).astype(np.float32)
    yd[:, 16:24] *= rng.uniform(size=(K, 1)) < 0.5
    yd[8:16, 32:40] = 0.15               # nonzero blocks under eps
    yd[24:32, 8:16] = -0.1
    x = _coo_of(xd)
    eng = DynasparseEngine(tile_m=16, tile_n=32, literal=True, eps=eps)
    plan = _all_spmm(eng.plan(x, jnp.asarray(yd)))
    z_c = np.asarray(eng.execute(plan, x, jnp.asarray(yd)))
    assert eng.cache.stats.dispatch_builds == 1
    np.testing.assert_array_equal(
        z_c, stripe_walk_reference(plan, xd, yd, eps=eps))
    z_p = pertask_reference(plan.part, plan.stq, [], xd, yd, eps=eps)
    np.testing.assert_allclose(z_c, z_p, rtol=1e-4, atol=1e-4)
    xm = eps_masked(xd, eps)
    np.testing.assert_allclose(z_c, xm @ eps_masked(yd, eps),
                               rtol=1e-4, atol=1e-4)
    assert np.abs(z_c - xm @ yd).max() > 1e-2


def test_misaligned_geometry_declines_compiled_but_matches():
    """tile_m=12 interior boundaries can't take the in-place index maps:
    the engine refuses the geometry before it plans or lowers anything,
    and the same kernel at an aligned tile compiles and matches."""
    rng = np.random.default_rng(3)
    xd = (rng.normal(size=(36, 24)) *
          (rng.uniform(size=(36, 24)) < 0.3)).astype(np.float32)
    yd = rng.normal(size=(24, 16)).astype(np.float32)
    x = _coo_of(xd)
    eng = DynasparseEngine(tile_m=12, tile_n=8, literal=True)
    with pytest.raises(ValueError, match="tile_m=12"):
        eng.plan(x, jnp.asarray(yd))
    with pytest.raises(ValueError, match="lcm"):
        eng.matmul(x, jnp.asarray(yd))
    assert eng.cache.plan_count() == 0
    assert eng.cache.stats.dispatch_builds == 0
    aligned = DynasparseEngine(tile_m=16, tile_n=8, literal=True)
    z, _ = aligned.matmul(x, jnp.asarray(yd))
    assert aligned.cache.stats.dispatch_builds == 1
    np.testing.assert_allclose(np.asarray(z), xd @ yd, rtol=1e-4, atol=1e-4)


def test_dispatch_entries_byte_accounted_and_evictable():
    """A cached dispatch must charge its descriptor/pool bytes and obey the
    LRU byte budget like every other entry kind."""
    xd, yd = _mixed_ragged_operands(seed=6)
    x = _coo_of(xd)
    eng = DynasparseEngine(tile_m=32, tile_n=24, literal=True)
    before = eng.cache.bytes_used
    eng.matmul(x, jnp.asarray(yd))
    assert eng.cache.dispatch_count() == 1
    assert eng.cache.bytes_used > before

    small = PlanCache(max_bytes=1)      # everything but the newest evicts
    eng2 = DynasparseEngine(tile_m=32, tile_n=24, literal=True, cache=small)
    eng2.matmul(x, jnp.asarray(yd))
    assert small.stats.evictions > 0
    assert small.bytes_used <= max(
        nb for _, nb in small._entries.values()) or len(small) == 1


def test_replan_same_assignment_reuses_dispatch():
    """The dispatch key is content-addressed on (structure, assignment):
    a drift replan that lands on the same task assignment must HIT."""
    xd, yd = _mixed_ragged_operands(seed=8)
    x = _coo_of(xd)
    eng = DynasparseEngine(tile_m=32, tile_n=24, literal=True,
                           drift_threshold=1e-12)  # replan on any wiggle
    eng.matmul(x, jnp.asarray(yd))
    assert eng.cache.stats.dispatch_builds == 1
    # zero ONE element of a dense stripe: a sub-eps density wiggle that
    # trips the replan threshold but cannot flip any task's assignment
    yd2 = yd.copy()
    r, c = np.argwhere(yd2[:, 24:] != 0)[0]
    yd2[r, 24 + c] = 0.0
    eng.matmul(x, jnp.asarray(yd2))
    assert eng.cache.stats.replans >= 1
    assert eng.cache.stats.dispatch_builds == 1     # reused, not rebuilt
    assert eng.cache.stats.dispatch_hits >= 1


# --------------------------------------------------------- compile_model
@pytest.mark.parametrize("model", gnn.MODELS)
def test_compile_model_single_program_matches_eager(model):
    rng = np.random.default_rng(17)
    n, nnz = 80, 240
    flat = np.sort(rng.choice(n * n, size=nnz, replace=False))
    adj = SparseCOO((n, n), jnp.asarray((flat // n).astype(np.int32)),
                    jnp.asarray((flat % n).astype(np.int32)),
                    jnp.asarray(np.abs(rng.normal(size=nnz)
                                       ).astype(np.float32)),
                    tag="adjacency")
    h = rng.normal(size=(n, 12)).astype(np.float32)
    params = gnn.init_params(model, 12, 8, 5)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True)
    eng.reset()
    warm, cm = gnn.compile_model(model, eng, adj, jnp.asarray(h), params)
    assert cm is not None
    assert cm.n_sparse >= 1
    assert len(cm.report.kernels) == cm.n_kernels
    ref = gnn.run_reference(model, adj, jnp.asarray(h), params)
    np.testing.assert_allclose(np.asarray(warm), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)
    z1 = cm(jnp.asarray(h))
    z2 = cm(jnp.asarray(h))
    assert cm.calls == 2 and cm.traces == 1        # one trace, then hits
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))
    np.testing.assert_allclose(np.asarray(z1), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_compile_model_declines_on_nonliteral_engine():
    rng = np.random.default_rng(19)
    n, nnz = 40, 80
    flat = np.sort(rng.choice(n * n, size=nnz, replace=False))
    adj = SparseCOO((n, n), jnp.asarray((flat // n).astype(np.int32)),
                    jnp.asarray((flat % n).astype(np.int32)),
                    jnp.asarray(np.abs(rng.normal(size=nnz)
                                       ).astype(np.float32)),
                    tag="adjacency")
    h = rng.normal(size=(n, 10)).astype(np.float32)
    params = gnn.init_params("SGC", 10, 8, 8)
    eng = DynasparseEngine(tile_m=16, tile_n=8)     # literal=False
    warm, cm = gnn.compile_model("SGC", eng, adj, jnp.asarray(h), params)
    assert cm is None
    ref = gnn.run_reference("SGC", adj, jnp.asarray(h), params)
    np.testing.assert_allclose(np.asarray(warm), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def _served_operands(rng, n=80, nnz=240, feat=12):
    """A graph and block-sparse features, so GCN's transform plans sparse
    tasks on a dense operand (the ``act`` route) beside the adjacency's."""
    flat = np.sort(rng.choice(n * n, size=nnz, replace=False))
    adj = SparseCOO((n, n), jnp.asarray((flat // n).astype(np.int32)),
                    jnp.asarray((flat % n).astype(np.int32)),
                    jnp.asarray(np.abs(rng.normal(size=nnz)
                                       ).astype(np.float32)),
                    tag="adjacency")
    mask = np.kron(rng.uniform(size=(-(-n // 8), -(-feat // 8))) < 0.35,
                   np.ones((8, 8)))[:n, :feat]
    h = (rng.normal(size=(n, feat)) * mask).astype(np.float32)
    return adj, jnp.asarray(h)


@pytest.mark.parametrize("model,n_devices", [
    ("GCN", None), ("GIN", None), ("GraphSAGE", None), ("GCN", 1)])
def test_execute_and_compile_model_take_the_same_route(model, n_devices):
    """Every kernel of the warmup pass runs ``engine.execute`` through
    the route ``compile_model`` records for it: the same kind (``sparse``
    or, on a mesh, ``shard``; ``act``; ``gemm``) and the same descriptor
    arrays, not a second lowering."""
    from repro.launch.mesh import make_data_mesh

    adj, h = _served_operands(np.random.default_rng(23))
    params = gnn.init_params(model, 12, 8, 5)
    mesh = None if n_devices is None else make_data_mesh(n_devices)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True, mesh=mesh)
    routes = []
    execute = eng.execute

    def spy(plan, x, y):
        z = execute(plan, x, y)
        routes.append(eng.last_route)
        return z
    eng.execute = spy
    _, cm = gnn.compile_model(model, eng, adj, h, params)
    assert cm is not None and len(routes) == cm.n_kernels
    kinds = []
    for (kind, d), p in zip(routes, cm.payload):
        kinds.append(kind)
        if kind == "gemm":
            assert p is None and d is None
            continue
        assert ("xd" in p) == (kind in ("sparse", "shard"))
        assert all(p["arrays"][k] is v for k, v in d.arrays.items())
    sparse = "sparse" if mesh is None else "shard"
    assert set(kinds) <= {sparse, "act", "gemm"} and sparse in kinds
    assert cm.n_sparse == kinds.count(sparse)
    assert cm.n_act == kinds.count("act")
    if model == "GCN":
        assert "act" in kinds


@pytest.mark.parametrize("model", gnn.MODELS)
def test_warm_batch_logits_equal_next_compiled_batch(model):
    """The eager warmup batch and the compiled batch after it run the same
    routes on the same input, with the activation block-skip on: their
    logits agree bit for bit, request by request."""
    from repro.serving import ServingConfig, ServingEngine, SharedPlanCache

    rng = np.random.default_rng(29)
    adj, h = _served_operands(rng)
    params = gnn.init_params(model, 12, 8, 5)
    reqs = [("g", h * (1.0 + 0.1 * i)) for i in range(4)]
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True,
                           cache=SharedPlanCache())
    with ServingEngine(model, params, engine=eng, config=ServingConfig(
            max_batch=4, activation_skip=True)) as srv:
        srv.register_graph("g", adj)
        warm = srv.serve(reqs)
        assert srv.stats.compiled_batches == 0
        compiled = srv.serve(reqs)
        assert srv.stats.compiled_batches == 1
    if model == "GCN":
        assert srv.dispatch_stats()["act_kernels_last"] >= 1
    for a, b in zip(warm, compiled):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
