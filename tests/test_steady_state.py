"""Steady-state serving gates at the 128-vertex fixture.

A 128-vertex graph (average degree 4) is served through ``ServingEngine`` on
a literal engine, GCN with 24 input features and hidden width 16, 48
requests in micro-batches of 8.  The first micro-batch is the eager warmup
that plans, packs and lowers every kernel and compiles the whole-model
program; these tests hold what every later batch must then be:

- each planned kernel lowered once (``dispatch_builds == plans``), nothing
  replanned;
- every post-warmup micro-batch one compiled call, each after the first a
  trace-cache hit;
- on block-sparse features, the activation block-skip route active, with
  no budget overflow and no retrace while the pattern wiggles;
- a restarted ``SharedPlanCache`` replaying the calibration with zero
  re-measures;
- per-stripe activation budgets cutting padded slots by at least 20%
  against the uniform budget, bit for bit the same result;
- the mesh-sharded and halo-sharded dispatches bit-exact against the exact
  reference and the replicate-everything oracle on every visible device
  (the strictly-smaller-per-device checks need at least 4 devices and skip
  below that).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DynasparseEngine, SparseCOO, calibrate
from repro.core import dispatch as dispatch_mod
from repro.core.perfmodel import runtime_fallback
from repro.launch.mesh import make_data_mesh
from repro.models import gnn
from repro.serving import ServingConfig, ServingEngine, SharedPlanCache
from spmm_reference import activation_reference, stripe_walk_reference

N, FEAT, HIDDEN, REQUESTS, MAX_BATCH = 128, 24, 16, 48, 8


def _fixed_graph(n: int = N, avg_deg: int = 4, seed: int = 5) -> SparseCOO:
    rng = np.random.default_rng(seed)
    flat = np.sort(rng.choice(n * n, size=avg_deg * n, replace=False))
    return SparseCOO((n, n),
                     jnp.asarray((flat // n).astype(np.int32)),
                     jnp.asarray((flat % n).astype(np.int32)),
                     jnp.asarray(np.abs(rng.normal(size=avg_deg * n)
                                        ).astype(np.float32)),
                     tag="adjacency")


def _serve(adj, batches):
    """Serve ``batches`` as one stream; returns (engine, logits, ref err)."""
    params = gnn.init_params("GCN", FEAT, HIDDEN, HIDDEN)
    eng = DynasparseEngine(tile_m=32, tile_n=8, literal=True,
                           cache=SharedPlanCache())
    with ServingEngine("GCN", params, engine=eng,
                       config=ServingConfig(max_batch=MAX_BATCH)) as srv:
        srv.register_graph("bench", adj)
        outs = srv.serve(("bench", h) for h in batches)
    err = max(float(np.max(np.abs(np.asarray(z) - np.asarray(
        gnn.run_reference("GCN", adj, jnp.asarray(h), params)))))
        for z, h in zip(outs[:2], batches[:2]))
    return srv, err


@pytest.fixture(scope="module")
def steady():
    rng = np.random.default_rng(0)
    batches = [rng.normal(size=(N, FEAT)).astype(np.float32)
               for _ in range(REQUESTS)]
    return _serve(_fixed_graph(), batches)


@pytest.fixture(scope="module")
def sparse_stream():
    """Block-sparse features whose pattern wiggles per request."""
    rng = np.random.default_rng(3)
    B = 8
    mask = np.kron(rng.uniform(size=(N // B, FEAT // B)) < 0.3,
                   np.ones((B, B)))
    batches = [(rng.normal(size=(N, FEAT)) * mask
                * (rng.uniform(size=(N, FEAT)) < 0.95)).astype(np.float32)
               for _ in range(REQUESTS)]
    return _serve(_fixed_graph(), batches)


def test_steady_state_matches_reference(steady):
    _, err = steady
    assert err < 1e-3


def test_dispatch_builds_equal_plans(steady):
    """Every planned kernel was lowered exactly once; nothing re-derived."""
    srv, _ = steady
    ds = srv.dispatch_stats()
    assert ds["plans"] > 0
    assert ds["dispatch_builds"] == ds["plans"]


def test_no_replans(steady):
    srv, _ = steady
    assert srv.dispatch_stats()["replans"] == 0


def test_every_post_warmup_batch_runs_compiled(steady):
    srv, _ = steady
    assert srv.stats.batches == REQUESTS // MAX_BATCH
    assert srv.stats.compiled_batches == srv.stats.batches - 1
    assert srv.stats.degraded_batches == 0


def test_trace_cache_hit_on_every_compiled_batch_after_the_first(steady):
    srv, _ = steady
    (cm,) = srv._compiled.values()
    assert cm.calls == srv.stats.compiled_batches and cm.traces == 1
    ds = srv.dispatch_stats()
    assert ds["trace_cache_hits"] >= srv.stats.compiled_batches - 1 > 0


def test_sparse_activation_stream_skips_blocks_without_overflow(
        sparse_stream):
    """Post-warmup batches keep the block-skip route active across varying
    patterns: a real skipped-block ratio, credited descriptor hits, no
    budget overflow, no replan, no recompile and no retrace."""
    srv, err = sparse_stream
    assert err < 1e-3
    ds = srv.dispatch_stats()
    st = srv.stats
    assert st.compiled_batches == st.batches - 1
    assert len(st.activation_batches) == st.compiled_batches
    assert ds["act_kernels_last"] >= 1
    assert ds["act_skipped_ratio_mean"] > 0.0
    assert ds["act_hits"] > 0
    assert ds["act_overflows"] == 0
    assert ds["replans"] == 0 and st.compile_invalidations == 0
    (cm,) = srv._compiled.values()
    assert cm.traces == 1


def test_restart_replays_calibration_with_zero_remeasures(tmp_path,
                                                          monkeypatch):
    """A fallback model is measured once; a SharedPlanCache saved and
    loaded into a fresh process-level cache resolves the same calibrated
    model without timing a single kernel."""
    monkeypatch.delenv(calibrate.SNAPSHOT_ENV, raising=False)
    adj = _fixed_graph()
    y = jnp.asarray(np.random.default_rng(1).normal(
        size=(N, 16)).astype(np.float32))
    cache = SharedPlanCache()
    eng = DynasparseEngine(runtime_fallback(), tile_m=32, tile_n=8,
                           literal=True, cache=cache, calibration="auto")
    n0 = calibrate.measurement_count()
    z, _ = eng.matmul(adj, y)
    assert calibrate.measurement_count() > n0
    hw = eng.runtime_hw()
    assert hw.calibrated
    np.testing.assert_allclose(np.asarray(z), adj.todense() @ np.asarray(y),
                               rtol=1e-4, atol=1e-4)

    snap = os.path.join(tmp_path, "cache.pkl")
    cache.save(snap)
    fresh = SharedPlanCache()
    fresh.load(snap)
    n1 = calibrate.measurement_count()
    restarted = DynasparseEngine(runtime_fallback(), tile_m=32, tile_n=8,
                                 literal=True, cache=fresh,
                                 calibration="auto")
    assert restarted.runtime_hw() == hw
    assert calibrate.measurement_count() == n1
    assert fresh.stats.calib_builds == 0 and fresh.stats.calib_hits == 1


def test_per_stripe_budgets_cut_padded_slots_bit_identically():
    """One dense row-stripe, the rest nearly empty: the per-stripe budget
    vector pays each stripe its own need.  Padded-slot waste drops by at
    least 20% against the uniform budget, no jittered batch overflows or
    retraces either budget, and both give the eager pass's result."""
    rng = np.random.default_rng(7)
    m, k, width, B = 96, 64, 16, 8
    x = np.zeros((m, k), np.float32)
    x[:16] = rng.normal(size=(16, k))
    mask = np.kron(rng.uniform(size=((m - 16) // B, k // B)) < 0.06,
                   np.ones((B, B)))
    x[16:] = rng.normal(size=(m - 16, k)) * mask
    y = rng.normal(size=(k, width)).astype(np.float32)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True,
                           cache=SharedPlanCache())
    plan = eng.plan(x, jnp.asarray(y), name="act")
    ad_u = eng.activation_dispatch_for(plan, x, per_stripe=False)
    ad_v = eng.activation_dispatch_for(plan, x, per_stripe=True)
    assert ad_u is not None and ad_v is not None
    stats = eng.cache.stats
    jitter = rng.uniform(size=(4, m, k)) < 0.9
    z_eager = np.asarray(eng.execute(plan, x, jnp.asarray(y)))
    assert eng.last_route == ("act", ad_v)
    np.testing.assert_array_equal(z_eager, activation_reference(plan, x, y))

    for ad in (ad_u, ad_v):
        traces0 = stats.trace_builds
        z, diag = dispatch_mod.execute_activation(ad, x, y, interpret=True,
                                                  stats=stats)
        np.testing.assert_array_equal(np.asarray(z), z_eager)
        assert not bool(diag["overflow"])
        for keep in jitter:
            _, d = dispatch_mod.execute_activation(
                ad, (x * keep).astype(np.float32), y, interpret=True,
                stats=stats)
            assert not bool(d["overflow"])
        assert stats.trace_builds - traces0 <= 1

    stored = int(diag["stored"])
    waste_u = ad_u.geom.total_slots - stored
    waste_v = ad_v.geom.total_slots - stored
    assert waste_v <= 0.8 * waste_u, (waste_u, waste_v)


def _mesh_case(adj, width, seed, **kw):
    """(engine, plan, y, compiled z) of one sharded aggregation kernel over
    every visible device, executed once to lower and trace it."""
    y = jnp.asarray(np.random.default_rng(seed).normal(
        size=(adj.shape[0], width)).astype(np.float32))
    eng = DynasparseEngine(tile_m=32, tile_n=8, literal=True,
                           cache=SharedPlanCache(),
                           mesh=make_data_mesh(len(jax.devices())), **kw)
    plan = eng.plan(adj, y, name="agg")
    return eng, plan, y, np.asarray(eng.execute(plan, adj, y))


def _banded_graph(n=256, deg=6, band=24, seed=4) -> SparseCOO:
    """Every edge within ``band`` rows of the diagonal: a thin halo."""
    rng = np.random.default_rng(seed)
    rows = np.sort(rng.integers(0, n, deg * n)).astype(np.int32)
    cols = np.clip(rows + rng.integers(-band, band + 1, deg * n),
                   0, n - 1).astype(np.int32)
    return SparseCOO((n, n), jnp.asarray(rows), jnp.asarray(cols),
                     jnp.asarray(np.abs(rng.normal(size=deg * n)
                                        ).astype(np.float32)),
                     tag="adjacency")


def _needs_four_devices():
    if len(jax.devices()) < 4:
        pytest.skip("needs a host exposing at least 4 devices")


def test_multidev_sharded_dispatch_bit_exact():
    """Row-stripe bands sharded over every visible device: bit for bit the
    exact reference of the same placed plan, one lowering, and every later
    call replayed without a retrace."""
    adj = _fixed_graph()
    eng, plan, y, z = _mesh_case(adj, 16, 2)
    np.testing.assert_array_equal(
        z, stripe_walk_reference(plan, adj.todense(), y, eps=eng.eps))
    traces0 = eng.cache.stats.trace_builds
    for _ in range(3):
        np.testing.assert_array_equal(
            np.asarray(eng.execute(plan, adj, y)), z)
    assert eng.cache.sharded_count() == 1
    assert eng.cache.stats.trace_builds == traces0
    assert eng.cache.stats.dispatch_hits > 0
    assert sum(plan.placement.band_sizes()) == plan.part.n_row_tiles


def test_multidev_shards_carry_fewer_descriptors_than_the_global_stream():
    _needs_four_devices()
    adj = _fixed_graph()
    eng, plan, _, _ = _mesh_case(adj, 16, 2)
    sd = eng.sharded_dispatch_for(plan, adj)
    per_dev = sum(int(sd.arrays[k].shape[-1])
                  for k in ("sp_a_ids", "mm_a_ids", "gemm_rows")
                  if k in sd.arrays)
    _, entry = eng._packed_structure(plan, adj)
    d = dispatch_mod.build_dispatch(plan.part, plan.stq, plan.dtq,
                                    entry.stripes, block=eng.block)
    glob = d.sparse_steps + (int(d.arrays["gemm_rows"].shape[-1])
                             if "gemm_rows" in d.arrays else 0)
    assert per_dev < glob


def test_halo_bit_exact_to_replicate_oracle():
    """Owned+halo operands on a banded graph over every visible device:
    bit for bit the replicate-everything oracle and the exact reference,
    one lowering replayed without a retrace."""
    adj = _banded_graph()
    eng, plan, y, z = _mesh_case(adj, 16, 4)
    assert eng.operand_sharding == "halo"
    _, _, _, z_r = _mesh_case(adj, 16, 4, operand_sharding="replicate")
    np.testing.assert_array_equal(z, z_r)
    np.testing.assert_array_equal(
        z, stripe_walk_reference(plan, adj.todense(), y, eps=eng.eps))
    traces0 = eng.cache.stats.trace_builds
    np.testing.assert_array_equal(np.asarray(eng.execute(plan, adj, y)), z)
    assert eng.cache.sharded_count() == 1
    assert eng.cache.stats.trace_builds == traces0


def test_halo_holds_fewer_operand_bytes_than_replication():
    _needs_four_devices()
    adj = _banded_graph()
    eng, plan, _, _ = _mesh_case(adj, 16, 4)
    ob = eng.sharded_dispatch_for(plan, adj).operand_bytes
    assert ob["halo_per_device_bytes"] < ob["replicated_per_device_bytes"]
