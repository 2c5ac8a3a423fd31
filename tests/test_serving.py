"""Serving subsystem: async micro-batched inference equivalence + stats.

The load-bearing property: a micro-batch of k stacked requests produces,
per request, the SAME logits as a per-request ``run_reference`` — the
column-stack / row-unstack transport around the engine kernels never mixes
requests.  Plus: coalescing behaviour, per-request stats, density-drift
replanning, and the run_serving thin-wrapper contract.
"""
import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DynasparseEngine, SparseCOO
from repro.models import gnn
from repro.serving import (ServingConfig, ServingEngine, SharedPlanCache,
                           SketchConfig)

RNG = np.random.default_rng(7)


def _rand_graph(n=80, nnz=240, seed=5):
    rng = np.random.default_rng(seed)
    flat = np.sort(rng.choice(n * n, size=nnz, replace=False))
    return SparseCOO((n, n),
                     jnp.asarray((flat // n).astype(np.int32)),
                     jnp.asarray((flat % n).astype(np.int32)),
                     jnp.asarray(np.abs(rng.normal(size=nnz)
                                        ).astype(np.float32)),
                     tag="adjacency")


def _serving(model, params, *, max_batch=4, literal=True,
             drift=0.25, cache=None, pad=True):
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=literal,
                           cache=cache if cache is not None
                           else SharedPlanCache())
    cfg = ServingConfig(max_batch=max_batch,
                        sketch=SketchConfig(threshold=drift),
                        pad_to_max_batch=pad)
    return ServingEngine(model, params, engine=eng, config=cfg)


# ------------------------------------------------------------ equivalence
@pytest.mark.parametrize("model", gnn.MODELS)
def test_micro_batched_matches_per_request_reference(model):
    adj = _rand_graph()
    params = gnn.init_params(model, 12, 8, 5)
    srv = _serving(model, params, max_batch=4)
    srv.register_graph("g", adj)
    batches = [RNG.normal(size=(80, 12)).astype(np.float32)
               for _ in range(6)]
    outs = srv.serve(("g", h) for h in batches)
    assert srv.stats.batches < len(batches)          # actually coalesced
    for h, z in zip(batches, outs):
        ref = gnn.run_reference(model, adj, jnp.asarray(h), params)
        np.testing.assert_allclose(np.asarray(z), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)


def test_latency_covers_block_until_ready_of_the_result(monkeypatch):
    """The serving timer stops only after the logits are ready on the
    device: dispatch returns before the device finishes, so a latency taken
    without blocking would measure enqueue time."""
    import time

    import jax

    adj = _rand_graph()
    params = gnn.init_params("GCN", 12, 8, 5)
    srv = _serving("GCN", params, max_batch=4, literal=False)
    srv.register_graph("g", adj)
    real = jax.block_until_ready
    blocked = []

    def slow_block(x):
        time.sleep(0.2)
        blocked.append(x)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", slow_block)
    outs = srv.serve([("g", RNG.normal(size=(80, 12)).astype(np.float32))
                      for _ in range(2)])
    srv.close()
    # one block per micro-batch, on the stacked logits the requests share
    assert len(blocked) == srv.stats.batches == 1
    assert blocked[0].shape == (80, 4 * outs[0].shape[1])
    for r in srv.stats.requests:
        assert r.t_execute >= 0.2 and r.latency >= 0.2


def test_coalescing_respects_max_batch_and_records_stats():
    adj = _rand_graph(seed=9)
    params = gnn.init_params("GCN", 12, 8, 5)
    srv = _serving("GCN", params, max_batch=4)
    srv.register_graph("g", adj)
    srv.serve(("g", RNG.normal(size=(80, 12)).astype(np.float32))
              for _ in range(10))
    stats = srv.stats
    assert len(stats.requests) == 10
    assert stats.batches == 3                         # 4 + 4 + 2
    assert sorted(r.batch_size for r in stats.requests) == [2, 2] + [4] * 8
    assert all(r.latency >= r.t_queue >= 0.0 for r in stats.requests)
    assert all(r.report is not None for r in stats.requests)
    depths = [r.queue_depth for r in stats.requests]
    assert max(depths) > 0                            # queue actually built up
    pct = stats.latency_percentiles()
    assert pct["p95"] >= pct["p50"] > 0.0


def test_one_plan_execute_pass_per_micro_batch():
    """k coalesced requests must issue ONE engine kernel sequence, not k."""
    adj = _rand_graph(seed=3)
    params = gnn.init_params("GCN", 12, 8, 8)
    srv = _serving("GCN", params, max_batch=8)
    srv.register_graph("g", adj)
    srv.serve(("g", RNG.normal(size=(80, 12)).astype(np.float32))
              for _ in range(8))
    assert srv.stats.batches == 1
    # the shared micro-batch report holds one kernel sequence (4 GCN mms)
    rep = srv.stats.requests[0].report
    assert len(rep.kernels) == 4


def test_multi_graph_requests_do_not_mix():
    adj_a, adj_b = _rand_graph(seed=1), _rand_graph(seed=2)
    params = gnn.init_params("GCN", 12, 8, 5)
    cache = SharedPlanCache()
    srv = _serving("GCN", params, max_batch=4, cache=cache)
    srv.register_graph("a", adj_a)
    srv.register_graph("b", adj_b)
    h = RNG.normal(size=(80, 12)).astype(np.float32)
    outs = srv.serve([("a", h), ("b", h), ("a", h)])
    ref_a = gnn.run_reference("GCN", adj_a, jnp.asarray(h), params)
    ref_b = gnn.run_reference("GCN", adj_b, jnp.asarray(h), params)
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(ref_a),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(outs[1]), np.asarray(ref_b),
                               rtol=1e-3, atol=1e-3)
    # same request content ⇒ same answer (up to primitive choice: the
    # balanced strategy may route a tile of one copy to the other queue)
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[2]),
                               rtol=1e-5, atol=1e-5)
    assert set(cache.graphs) == {"a", "b"}


def test_partial_batch_padding_matches_reference():
    """A partial micro-batch (k < max_batch) is padded to the max_batch
    stacked width (replicated columns); the padding must be an exact
    no-op per request."""
    adj = _rand_graph(seed=13)
    params = gnn.init_params("GCN", 12, 8, 5)
    srv = _serving("GCN", params, max_batch=8)
    srv.register_graph("g", adj)
    batches = [RNG.normal(size=(80, 12)).astype(np.float32)
               for _ in range(3)]
    outs = srv.serve(("g", h) for h in batches)
    assert srv.stats.batches == 1                     # one padded batch of 3
    assert [r.batch_size for r in srv.stats.requests] == [3, 3, 3]
    for h, z in zip(batches, outs):
        assert z.shape == (80, 5)                     # padding sliced away
        ref = gnn.run_reference("GCN", adj, jnp.asarray(h), params)
        np.testing.assert_allclose(np.asarray(z), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)


def test_single_plan_across_batch_sizes():
    """With pad_to_max_batch, serving k ∈ {1..max_batch} must create exactly
    one plan entry per graph/layer kernel — not one per batch size."""
    adj = _rand_graph(seed=14)
    params = gnn.init_params("GCN", 12, 8, 5)   # hidden != out: 2 agg widths
    cache = SharedPlanCache()
    srv = _serving("GCN", params, max_batch=4, cache=cache)
    srv.register_graph("g", adj)
    h = RNG.normal(size=(80, 12)).astype(np.float32)
    ref = gnn.run_reference("GCN", adj, jnp.asarray(h), params)
    for k in (1, 2, 3, 4):
        outs = srv.serve([("g", h)] * k)
        for z in outs:
            np.testing.assert_allclose(np.asarray(z), np.asarray(ref),
                                       rtol=1e-3, atol=1e-3)
    # one plan per aggregation kernel geometry (GCN: l1-agg and l2-agg have
    # different widths), regardless of the four distinct batch sizes
    assert cache.plan_count() == 2

    # without padding, every distinct batch size plans its own width
    cache2 = SharedPlanCache()
    srv2 = _serving("GCN", params, max_batch=4, cache=cache2, pad=False)
    srv2.register_graph("g", adj)
    for k in (1, 2, 3, 4):
        srv2.serve([("g", h)] * k)
    assert cache2.plan_count() == 2 * 4


def test_padded_partial_batches_do_not_thrash_replanner():
    """Mixed full/partial traffic with stable content must trigger ZERO
    density-drift replans: the padding replicates real feature columns, so
    the padded operand's density matches a full batch's (zero-padding here
    would register ~1.0 drift on every fill change and replan per batch,
    defeating single-plan serving)."""
    adj = _rand_graph(seed=19)
    params = gnn.init_params("GCN", 12, 8, 5)
    cache = SharedPlanCache()
    srv = _serving("GCN", params, max_batch=4, cache=cache)   # drift=0.25
    srv.register_graph("g", adj)
    h = RNG.normal(size=(80, 12)).astype(np.float32)
    for k in (4, 1, 4, 1, 4):
        srv.serve([("g", h)] * k)
    assert cache.stats.replans == 0
    assert cache.plan_count() == 2            # still one plan per agg kernel


def test_serve_inside_running_loop():
    """serve() must work when the calling thread already runs an event loop
    (notebooks, async callers) — asyncio.run would raise RuntimeError."""
    adj = _rand_graph(seed=15)
    params = gnn.init_params("GCN", 12, 8, 5)
    srv = _serving("GCN", params, max_batch=2)
    srv.register_graph("g", adj)
    h = RNG.normal(size=(80, 12)).astype(np.float32)

    async def main():
        return srv.serve([("g", h), ("g", h)])

    outs = asyncio.run(main())
    assert len(outs) == 2
    ref = gnn.run_reference("GCN", adj, jnp.asarray(h), params)
    for z in outs:
        np.testing.assert_allclose(np.asarray(z), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)


def test_failed_requests_recorded_in_stats():
    """A mixed-width micro-batch is bisected by the degradation ladder: the
    well-formed request is served alone, the poison one fails ALONE with
    `error` recorded — failed traffic may not undercount, and a bad
    neighbour may not take the batch down with it."""
    adj = _rand_graph(seed=16)
    params = gnn.init_params("GCN", 12, 8, 5)
    srv = _serving("GCN", params, max_batch=2)
    srv.register_graph("g", adj)
    h_a = RNG.normal(size=(80, 12)).astype(np.float32)
    h_b = RNG.normal(size=(80, 13)).astype(np.float32)   # wrong fan-in
    with pytest.raises(ValueError):
        srv.serve([("g", h_a), ("g", h_b)])
    assert len(srv.stats.requests) == 2
    assert srv.stats.bisections >= 1
    assert srv.stats.errors == 1
    assert srv.stats.quarantined == 1
    bad = [r for r in srv.stats.requests if r.error is not None]
    assert len(bad) == 1 and bad[0].batch_size == 1
    good = [r for r in srv.stats.requests if r.error is None]
    assert len(good) == 1 and good[0].report is not None
    assert srv.stats.as_dict()["errors"] == 1
    # the well-formed request's logits were actually delivered
    outs = srv.serve([("g", h_a), ("g", h_b)], return_exceptions=True)
    assert not isinstance(outs[0], Exception)
    assert isinstance(outs[1], Exception)
    ref = gnn.run_reference("GCN", adj, jnp.asarray(h_a), params)
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_error_escaping_dispatch_fails_batch_instead_of_hanging():
    """An exception raised before the engine try-block (here: same widths
    but mismatched row counts, so the stacking concatenate throws) must
    never strand futures: the ladder bisects, serves the well-formed
    request, and quarantines the poison one with its error recorded."""
    adj = _rand_graph(seed=22)
    params = gnn.init_params("GCN", 12, 8, 5)
    srv = _serving("GCN", params, max_batch=2)
    srv.register_graph("g", adj)
    h_a = RNG.normal(size=(80, 12)).astype(np.float32)
    h_b = RNG.normal(size=(96, 12)).astype(np.float32)  # wrong row count
    with pytest.raises(Exception):
        srv.serve([("g", h_a), ("g", h_b)])
    assert len(srv.stats.requests) == 2
    assert srv.stats.errors == 1              # poison fails alone
    assert srv.stats.quarantined == 1
    assert len(srv.stats.batch_reports) == 1  # the good half's report


def test_serve_after_close_raises_instead_of_hanging():
    """Submitting against a closed engine must surface the executor's
    RuntimeError through the futures, not deadlock."""
    adj = _rand_graph(seed=23)
    params = gnn.init_params("GCN", 12, 8, 5)
    srv = _serving("GCN", params, max_batch=2)
    srv.register_graph("g", adj)
    srv.close()
    with pytest.raises(RuntimeError):
        srv.serve([("g", RNG.normal(size=(80, 12)).astype(np.float32))])
    assert srv.stats.errors == 1


def test_per_request_report_attribution():
    """Each request's report is its 1/k share of the micro-batch report; the
    raw batch report is kept on stats.batch_reports."""
    adj = _rand_graph(seed=17)
    params = gnn.init_params("GCN", 12, 8, 5)
    srv = _serving("GCN", params, max_batch=4)
    srv.register_graph("g", adj)
    srv.serve(("g", RNG.normal(size=(80, 12)).astype(np.float32))
              for _ in range(4))
    assert srv.stats.batches == 1
    assert len(srv.stats.batch_reports) == 1
    batch_rep = srv.stats.batch_reports[0]
    assert batch_rep.hardware_time > 0.0
    for r in srv.stats.requests:
        assert r.report.hardware_time == pytest.approx(
            batch_rep.hardware_time / 4)
        assert r.report.total.flops_executed == pytest.approx(
            batch_rep.total.flops_executed / 4)
        # the kernel sequence itself is shared (4 GCN matmuls)
        assert len(r.report.kernels) == len(batch_rep.kernels) == 4
    # shares sum back to the batch total
    assert sum(r.report.hardware_time for r in srv.stats.requests) == (
        pytest.approx(batch_rep.hardware_time))


def test_unregistered_graph_raises():
    srv = _serving("GCN", gnn.init_params("GCN", 12, 8, 5))
    with pytest.raises(KeyError, match="not registered"):
        asyncio.run(srv.infer("nope", np.zeros((4, 12), np.float32)))


def test_dispatch_error_fails_requests_instead_of_hanging():
    """An engine-side error inside a micro-batch must surface as the
    requests' exception — never strand their futures (serve() deadlock)."""
    adj = _rand_graph(seed=4)
    srv = _serving("GCN", gnn.init_params("GCN", 10, 8, 5), max_batch=2)
    srv.register_graph("g", adj)
    bad = RNG.normal(size=(80, 7)).astype(np.float32)   # fan-in mismatch
    with pytest.raises(ValueError):
        srv.serve([("g", bad), ("g", bad)])


def test_run_serving_restores_engine_drift_settings():
    adj = _rand_graph(seed=5)
    params = gnn.init_params("SGC", 10, 8, 8)
    eng = DynasparseEngine(tile_m=16, tile_n=8)
    assert eng.drift_threshold is None
    gnn.run_serving("SGC", eng, adj,
                    [RNG.normal(size=(80, 10)).astype(np.float32)], params)
    assert eng.drift_threshold is None      # no hidden mutation



# ------------------------------------------------- compiled-dispatch path
def test_compiled_serving_steady_state_stats_and_results():
    """After the warmup batch, EVERY micro-batch must run as one compiled
    call (zero descriptor builds, jit trace hits) and still match the
    per-request reference."""
    adj = _rand_graph(seed=31)
    params = gnn.init_params("GCN", 12, 8, 5)
    cache = SharedPlanCache()
    srv = _serving("GCN", params, max_batch=4, cache=cache)
    srv.register_graph("g", adj)
    batches = [RNG.normal(size=(80, 12)).astype(np.float32)
               for _ in range(16)]
    outs = srv.serve(("g", h) for h in batches)
    ds = srv.dispatch_stats()
    assert srv.stats.compiled_batches == srv.stats.batches - 1
    assert ds["dispatch_builds"] == ds["plans"]
    assert ds["replans"] == 0
    assert ds["trace_cache_hits"] > 0
    # every compiled batch after the first reused the whole-model trace
    assert ds["trace_cache_hits"] >= srv.stats.compiled_batches - 1
    # the sparse sections' grid steps of the program's adjacency kernels
    assert ds["sparse_steps"] == sum(
        int(p["arrays"][k].shape[0])
        for cm in srv._compiled.values() for p in cm.payload
        if p and "xd" in p
        for k in ("sp_a_ids", "mm_a_ids") if k in p["arrays"]) > 0
    for h, z in zip(batches, outs):
        ref = gnn.run_reference("GCN", adj, jnp.asarray(h), params)
        np.testing.assert_allclose(np.asarray(z), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)
    srv.close()


def test_compile_models_off_keeps_eager_path():
    adj = _rand_graph(seed=32)
    params = gnn.init_params("GCN", 12, 8, 5)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True,
                           cache=SharedPlanCache())
    srv = ServingEngine("GCN", params, engine=eng,
                        config=ServingConfig(max_batch=4,
                                             compile_models=False))
    srv.register_graph("g", adj)
    h = RNG.normal(size=(80, 12)).astype(np.float32)
    outs = srv.serve([("g", h)] * 8)
    assert srv.stats.compiled_batches == 0
    ref = gnn.run_reference("GCN", adj, jnp.asarray(h), params)
    for z in outs:
        np.testing.assert_allclose(np.asarray(z), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)
    srv.close()


def test_compiled_drift_invalidation_recompiles():
    """Input-density drift must drop the compiled program, replan through
    the eager pass, recompile, and stay reference-exact."""
    adj = _rand_graph(seed=33)
    params = gnn.init_params("GCN", 12, 8, 5)
    cache = SharedPlanCache()
    srv = _serving("GCN", params, max_batch=1, cache=cache)
    srv.register_graph("g", adj)
    sparse_h = (RNG.normal(size=(80, 12)) *
                (RNG.uniform(size=(80, 12)) < 0.03)).astype(np.float32)
    dense_h = RNG.normal(size=(80, 12)).astype(np.float32)
    outs = srv.serve([("g", sparse_h), ("g", sparse_h),
                      ("g", dense_h), ("g", dense_h)])
    assert srv.stats.compile_invalidations >= 1
    assert cache.stats.replans > 0
    ref = gnn.run_reference("GCN", adj, jnp.asarray(dense_h), params)
    for z in outs[2:]:
        np.testing.assert_allclose(np.asarray(z), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)
    srv.close()


def test_reregistered_graph_drops_stale_compiled_program():
    """Re-registering a graph_id with a DIFFERENT adjacency must not keep
    serving the old graph's compiled whole-model program (the input-density
    drift check cannot see an adjacency swap)."""
    adj_a, adj_b = _rand_graph(seed=41), _rand_graph(seed=42)
    params = gnn.init_params("GCN", 12, 8, 5)
    srv = _serving("GCN", params, max_batch=2)
    srv.register_graph("g", adj_a)
    h = RNG.normal(size=(80, 12)).astype(np.float32)
    srv.serve([("g", h)] * 4)                    # warm + compile against a
    assert srv.stats.compiled_batches >= 1
    srv.register_graph("g", adj_b)               # swap the graph in place
    outs = srv.serve([("g", h)] * 2)
    ref_b = gnn.run_reference("GCN", adj_b, jnp.asarray(h), params)
    for z in outs:
        np.testing.assert_allclose(np.asarray(z), np.asarray(ref_b),
                                   rtol=1e-3, atol=1e-3)
    srv.close()


def test_graph_scale_sparse_only_serving_never_densifies():
    """The graph-scale x=None batched path THROUGH the ServingEngine: an
    all-sparse plan must serve (compiled included) without ever
    materializing the densified adjacency."""
    adj = _rand_graph(seed=34, n=96, nnz=200)
    params = gnn.init_params("GCN", 12, 8, 5)
    cache = SharedPlanCache()
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True,
                           mode="sparse_only", cache=cache)
    srv = ServingEngine("GCN", params, engine=eng,
                        config=ServingConfig(max_batch=4))
    srv.register_graph("g", adj)
    batches = [RNG.normal(size=(96, 12)).astype(np.float32)
               for _ in range(8)]
    outs = srv.serve(("g", h) for h in batches)
    assert srv.stats.compiled_batches >= 1
    from repro.core.plancache import PlanCache, StructureEntry
    entries = [v for (kind, _k), v in cache.items()
               if kind == PlanCache._STRUCT]
    assert entries, "expected packed structure entries"
    assert all(isinstance(e, StructureEntry) and e.dense is None
               for e in entries)
    for h, z in zip(batches, outs):
        ref = gnn.run_reference("GCN", adj, jnp.asarray(h), params)
        np.testing.assert_allclose(np.asarray(z), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)
    srv.close()


@pytest.mark.parametrize("model,tile_n,walks,budget", [
    ("GIN", 8, True, None), ("GCN", 64, False, None), ("GIN", 8, True, 1)])
def test_dispatch_stats_count_whole_row_sections(model, tile_n, walks,
                                                 budget):
    """``row_walk_sections`` beside ``sparse_steps``: GIN's aggregations
    over 4 x 12 stacked features in 8-wide column tiles, every task on the
    sparse queue, walk whole rows; GCN's over 4 x 8 and 4 x 5 columns have
    one column tile each, so none does.  Both count the compiled program's
    own kernels, so a plan cache too small to keep their dispatches (a
    byte budget of 1) reads the same."""
    adj = _rand_graph(seed=35)
    params = gnn.init_params(model, 12, 8, 5)
    cache = SharedPlanCache(max_bytes=budget)
    eng = DynasparseEngine(tile_m=16, tile_n=tile_n, literal=True,
                           mode="sparse_only", cache=cache)
    srv = ServingEngine(model, params, engine=eng,
                        config=ServingConfig(max_batch=4))
    srv.register_graph("g", adj)
    batches = [RNG.normal(size=(80, 12)).astype(np.float32)
               for _ in range(8)]
    outs = srv.serve(("g", h) for h in batches)
    assert srv.stats.compiled_batches >= 1
    ds = srv.dispatch_stats()
    assert ds["sparse_steps"] == sum(
        int(p["arrays"][k].shape[0])
        for cm in srv._compiled.values() for p in cm.payload
        if p and "xd" in p
        for k in ("sp_a_ids", "mm_a_ids") if k in p["arrays"]) > 0
    assert (ds["row_walk_sections"] > 0) == walks
    cached = [d for (kind, _k), d in cache.items()
              if kind == cache._DISPATCH]
    if budget is None:
        assert ds["row_walk_sections"] == sum(
            d.row_walk_sections for d in cached)
    else:
        assert len(cached) < 2       # the program outlives its dispatches
    for h, z in zip(batches, outs):
        ref = gnn.run_reference(model, adj, jnp.asarray(h), params)
        np.testing.assert_allclose(np.asarray(z), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)
    srv.close()


# ------------------------------------------------------- density drift
def test_density_drift_triggers_replan_and_matches_reference():
    """Near-dense features swapped mid-stream: the sketch must catch the
    stale cached Y-densities, replan, and the result must stay exact."""
    adj = _rand_graph(seed=11)
    params = gnn.init_params("GCN", 12, 8, 5)
    cache = SharedPlanCache()
    srv = _serving("GCN", params, max_batch=1, cache=cache)
    srv.register_graph("g", adj)

    sparse_h = (RNG.normal(size=(80, 12)) *
                (RNG.uniform(size=(80, 12)) < 0.03)).astype(np.float32)
    dense_h = RNG.normal(size=(80, 12)).astype(np.float32)
    outs = srv.serve([("g", sparse_h), ("g", sparse_h), ("g", dense_h)])

    assert cache.stats.replans > 0                   # drift was caught
    ref = gnn.run_reference("GCN", adj, jnp.asarray(dense_h), params)
    np.testing.assert_allclose(np.asarray(outs[2]), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_no_drift_no_replan():
    adj = _rand_graph(seed=12)
    params = gnn.init_params("GCN", 12, 8, 5)
    cache = SharedPlanCache()
    srv = _serving("GCN", params, max_batch=1, cache=cache)
    srv.register_graph("g", adj)
    h = RNG.normal(size=(80, 12)).astype(np.float32)
    srv.serve([("g", h), ("g", h), ("g", h)])
    assert cache.stats.replans == 0
    assert cache.stats.plan_hits > 0                 # amortization intact


# ------------------------------------------------------- wrapper contract
def test_run_serving_wrapper_per_request_and_micro_batched():
    adj = _rand_graph(seed=21)
    params = gnn.init_params("SGC", 10, 8, 8)
    batches = [RNG.normal(size=(80, 10)).astype(np.float32)
               for _ in range(4)]

    outs1, reports1 = gnn.run_serving(
        "SGC", DynasparseEngine(tile_m=16, tile_n=8), adj, batches, params)
    outs4, reports4 = gnn.run_serving(
        "SGC", DynasparseEngine(tile_m=16, tile_n=8), adj, batches, params,
        max_batch=4)
    assert len(outs1) == len(outs4) == len(reports1) == len(reports4) == 4
    for h, z1, z4 in zip(batches, outs1, outs4):
        ref = gnn.run_reference("SGC", adj, jnp.asarray(h), params)
        np.testing.assert_allclose(np.asarray(z1), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(z4), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)
    # micro-batched: one engine pass for all four requests — they share one
    # attributed (1/k) report object; per-request runs each get their own
    assert reports4[0] is reports4[3]
    assert reports1[0] is not reports1[3]
