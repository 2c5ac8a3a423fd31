"""Plan/execute split: the engine's per-queue fused dispatch against the
per-task reference, and PlanCache behaviour (the paper's amortized Alg. 4
preprocessing)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DynasparseEngine, SparseCOO
from repro.core import dispatch as dispatch_mod
from repro.core.scheduler import ScheduleReport
from repro.core import sparsity
from repro.kernels import ops
from repro.models import gnn
from spmm_reference import pertask_reference

RNG = np.random.default_rng(99)


def _rand_graph(n=80, nnz=240, seed=5):
    """Random adjacency (no duplicate edges) tagged like the data loader's."""
    rng = np.random.default_rng(seed)
    flat = np.sort(rng.choice(n * n, size=nnz, replace=False))
    rows = (flat // n).astype(np.int32)
    cols = (flat % n).astype(np.int32)
    vals = np.abs(rng.normal(size=nnz)).astype(np.float32)
    return SparseCOO((n, n), jnp.asarray(rows), jnp.asarray(cols),
                     jnp.asarray(vals), tag="adjacency")


# --------------------------------------------------- batched == per-task
@pytest.mark.parametrize("model", gnn.MODELS)
def test_batched_dispatch_matches_pertask_and_reference(model):
    """The whole model on the engine's routes against the same model with
    every kernel's plan run task by task (``pertask_reference``)."""
    adj = _rand_graph()
    h = RNG.normal(size=(80, 12)).astype(np.float32)
    params = gnn.init_params(model, 12, 8, 5)
    eng_b = DynasparseEngine(tile_m=16, tile_n=8, literal=True)
    z_b, _ = gnn.run_inference(model, eng_b, adj, jnp.asarray(h), params)
    planner = DynasparseEngine(tile_m=16, tile_n=8, literal=True)

    def pertask_mm(x, y, name="kernel"):
        p = planner.plan(x, y, name=name)
        xd = x.todense() if isinstance(x, SparseCOO) else x
        return jnp.asarray(pertask_reference(p.part, p.stq, p.dtq, xd, y))

    z_p = gnn.APPLY[model](pertask_mm, adj, jnp.asarray(h), params)
    ref = gnn.run_reference(model, adj, jnp.asarray(h), params)
    np.testing.assert_allclose(np.asarray(z_b), np.asarray(z_p),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(z_b), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_batched_dispatch_mixed_queues_o_primitives_calls():
    """A kernel whose plan lands tasks in all three primitives must execute
    its compiled dispatch with one pallas launch per primitive, where the
    per-task reference takes one per task."""
    rng = np.random.default_rng(1)
    xd = rng.normal(size=(96, 64)).astype(np.float32)
    xd[:32] *= (rng.uniform(size=(32, 64)) < 0.01)
    xd[32:64] *= (rng.uniform(size=(32, 64)) < 0.3)
    yd = rng.normal(size=(64, 48)).astype(np.float32)
    yd[:, :24] *= (rng.uniform(size=(64, 24)) < 0.05)
    r, c = np.nonzero(xd)
    x = SparseCOO(xd.shape, jnp.asarray(r.astype(np.int32)),
                  jnp.asarray(c.astype(np.int32)),
                  jnp.asarray(xd[r, c]), tag="adjacency")

    eng = DynasparseEngine(tile_m=32, tile_n=24, literal=True)
    plan = eng.plan(x, jnp.asarray(yd))
    prims = {t.primitive for t in plan.stq} | {t.primitive for t in plan.dtq}
    n_tasks = len(plan.stq) + len(plan.dtq)
    assert prims == {"SpDMM", "SpMM", "GEMM"}, prims

    d = eng.dispatch_for(plan, x)
    ops.reset_pallas_call_count()
    z_b = dispatch_mod.apply_dispatch(d.geom, d.arrays, jnp.asarray(xd),
                                      jnp.asarray(yd), interpret=True)
    calls_batched = ops.pallas_call_count()
    ops.reset_pallas_call_count()
    z_p = pertask_reference(plan.part, plan.stq, plan.dtq, xd, yd)
    calls_pertask = ops.pallas_call_count()
    np.testing.assert_array_equal(
        np.asarray(eng.execute(plan, x, jnp.asarray(yd))), np.asarray(z_b))

    assert calls_batched == len(prims)       # O(primitives)
    assert calls_pertask == n_tasks          # O(tasks)
    np.testing.assert_allclose(np.asarray(z_b), np.asarray(z_p),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(z_b), xd @ yd, rtol=1e-4, atol=1e-4)


# --------------------------------------------------------- cache behaviour
def test_adjacency_packed_and_analyzed_once_across_gcn_layers():
    """2-layer GCN: both aggregation kernels share ONE packing and ONE
    density analysis of the adjacency; a second inference is all plan hits."""
    adj = _rand_graph(n=96, nnz=300, seed=7)
    h = RNG.normal(size=(96, 20)).astype(np.float32)
    params = gnn.init_params("GCN", 20, 16, 16)  # hidden == out: l2 plan hits
    eng = DynasparseEngine(tile_m=32, tile_n=8, literal=True)

    gnn.run_inference("GCN", eng, adj, jnp.asarray(h), params)
    assert eng.cache.stats.packs == 1
    assert eng.cache.stats.analyzes == 1
    assert eng.cache.stats.plan_hits >= 1    # layer-2 aggregation

    stats_after_first = eng.cache.stats.plan_misses
    z2, _ = gnn.run_inference("GCN", eng, adj, jnp.asarray(h), params)
    assert eng.cache.stats.packs == 1                       # still one packing
    assert eng.cache.stats.analyzes == 1
    assert eng.cache.stats.plan_misses == stats_after_first  # no new misses

    ref = gnn.run_reference("GCN", adj, jnp.asarray(h), params)
    np.testing.assert_allclose(np.asarray(z2), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_cached_plan_matches_uncached_result():
    """Hitting the cache must not change the numerical result or report."""
    adj = _rand_graph(n=64, nnz=180, seed=3)
    h = RNG.normal(size=(64, 16)).astype(np.float32)
    eng = DynasparseEngine(tile_m=32, tile_n=8, literal=True)
    z1, rep1 = eng.matmul(adj, jnp.asarray(h), name="agg")
    z2, rep2 = eng.matmul(adj, jnp.asarray(h), name="agg")
    assert eng.cache.stats.plan_hits == 1
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))
    assert rep1.makespan == rep2.makespan


def test_same_pattern_different_values_not_conflated():
    """The fingerprint must cover values: cached packed blocks carry them."""
    adj = _rand_graph(n=64, nnz=150, seed=21)
    h = RNG.normal(size=(64, 8)).astype(np.float32)
    eng = DynasparseEngine(tile_m=32, tile_n=8, literal=True)
    eng.matmul(adj, jnp.asarray(h))
    doubled = SparseCOO(adj.shape, adj.rows, adj.cols, adj.vals * 2.0,
                        tag="adjacency")
    z, _ = eng.matmul(doubled, jnp.asarray(h))
    np.testing.assert_allclose(np.asarray(z), doubled.todense() @ h,
                               rtol=1e-4, atol=1e-4)
    assert eng.cache.stats.packs == 2


def test_inner_dim_mismatch_raises_at_plan_time():
    adj = _rand_graph(n=64, nnz=150, seed=22)
    eng = DynasparseEngine(tile_m=32, tile_n=8, literal=True)
    with pytest.raises(ValueError, match="inner-dim mismatch"):
        eng.matmul(adj, jnp.ones((32, 8), jnp.float32))
    assert eng.cache.stats.packs == 0


def test_serving_path_reuses_plans():
    adj = _rand_graph(n=64, nnz=200, seed=11)
    params = gnn.init_params("SGC", 10, 8, 8)
    batches = [RNG.normal(size=(64, 10)).astype(np.float32) for _ in range(3)]
    eng = DynasparseEngine(tile_m=32, tile_n=8)
    outs, reports = gnn.run_serving("SGC", eng, adj, batches, params)
    assert len(outs) == 3 and len(reports) == 3
    # requests 2 and 3 re-plan nothing for the adjacency kernels
    assert eng.cache.stats.plan_hits >= 2 * 2   # 2 agg kernels x 2 requests
    for h, z in zip(batches, outs):
        ref = gnn.run_reference("SGC", adj, jnp.asarray(h), params)
        np.testing.assert_allclose(np.asarray(z), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)


# ------------------------------------------------------------- satellites
def test_engine_report_total_empty_is_zero():
    eng = DynasparseEngine()
    eng.reset()
    tot = eng.report.total
    assert isinstance(tot, ScheduleReport)
    assert tot.makespan == 0.0 and tot.n_stq == 0 and tot.flops_executed == 0.0
    assert eng.report.hardware_time == 0.0
    # zero() is merge's identity
    assert ScheduleReport.zero().merge(tot).makespan == 0.0


def test_eps_threads_through_density_helpers():
    """density / stripe_density / tile_density agree on near-zero values."""
    x = np.full((32, 16), 1e-9, dtype=np.float32)
    x[:8] = 1.0
    xj = jnp.asarray(x)
    eps = 1e-6
    d = float(sparsity.density(xj, eps=eps))
    sd = np.asarray(sparsity.stripe_density(xj, 8, axis=0, eps=eps))
    td = np.asarray(sparsity.tile_density(xj, 8, 8, eps=eps))
    assert d == pytest.approx(0.25)
    np.testing.assert_allclose(sd, [1.0, 0.0, 0.0, 0.0])
    assert float(td.mean()) == pytest.approx(0.25)
    # without eps all three report fully dense — they must disagree together,
    # never with each other
    assert float(sparsity.density(xj)) == 1.0
    np.testing.assert_allclose(
        np.asarray(sparsity.stripe_density(xj, 8, axis=0)), [1.0] * 4)


def test_engine_eps_routes_near_zero_stripes_to_sparse_queue():
    x = np.full((64, 64), 1e-9, dtype=np.float32)
    x[:16] = RNG.normal(size=(16, 64)).astype(np.float32)
    y = RNG.normal(size=(64, 8)).astype(np.float32)
    eng = DynasparseEngine(tile_m=16, tile_n=8, eps=1e-6)
    plan = eng.plan(jnp.asarray(x), jnp.asarray(y))
    np.testing.assert_allclose(plan.row_density, [1.0, 0.0, 0.0, 0.0])
    z, _ = eng.matmul(jnp.asarray(x), jnp.asarray(y))
    np.testing.assert_allclose(np.asarray(z), x @ y, rtol=1e-4, atol=1e-4)


def test_coo_row_stripe_density_eps():
    rows = jnp.asarray(np.array([0, 10, 20, 30], dtype=np.int32))
    cols = jnp.asarray(np.zeros(4, dtype=np.int32))
    vals = jnp.asarray(np.array([1.0, 1e-9, 1.0, 1e-9], dtype=np.float32))
    a = SparseCOO((40, 4), rows, cols, vals)
    np.testing.assert_allclose(a.row_stripe_density(10),
                               [1 / 40, 1 / 40, 1 / 40, 1 / 40])
    np.testing.assert_allclose(a.row_stripe_density(10, eps=1e-6),
                               [1 / 40, 0.0, 1 / 40, 0.0])
