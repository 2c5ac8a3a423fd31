"""Sparse-activation capacity block-skip (tentpole of ISSUE 5).

Compiled whole-model programs used to freeze every activation-side kernel as
a plain dense GEMM; the capacity-padded BlockCSR route packs the activation
ON DEVICE into a fixed stored-block budget so compiled programs skip zero
blocks of intermediate features with fixed shapes.  These tests pin:

- bit-identity of the compiled block-skip route against its unfused
  reference (host-packed, ``spmm_reference``) across ragged
  shapes, primitives, eps values, dtypes, and capacities (exact / slack),
  and the engine's eager pass running that same route;
- the overflow semantics: a batch past the budget takes the dense-GEMM
  fallback INSIDE the same program (bit-identical to the plain dense route),
  never a retrace;
- shape stability: one trace serves any activation sparsity within budget;
- content-independent descriptor caching (act_builds / act_hits);
- the whole-model compiler choosing block-skip vs dense per layer and the
  serving steady state exposing the skip telemetry.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import DynasparseEngine, SparseCOO
from repro.core import dispatch as dispatch_mod
from repro.kernels import ops
from repro.kernels.formats import BlockCSR, pack_blockcsr
from repro.models import gnn
from spmm_reference import activation_reference


def _block_sparse(rng, m, k, block_density, *, block=8, dtype=np.float32):
    """Dense matrix whose zero pattern is block-structured (the shape of
    post-ReLU feature sparsity the block-skip route exploits)."""
    nrb, ncb = -(-m // block), -(-k // block)
    mask = (rng.uniform(size=(nrb, ncb)) < block_density).astype(np.float32)
    full = rng.normal(size=(nrb * block, ncb * block))
    x = (full * np.kron(mask, np.ones((block, block))))[:m, :k]
    return x.astype(dtype)


def _reference(eng, plan, xd, yd):
    return activation_reference(plan, xd, yd, eps=eng.eps, block=eng.block)


def _routes(eng, xd, yd, *, capacity=None, slack=1.5):
    """(plan, act dispatch, compiled z, diag, reference z)."""
    plan = eng.plan(xd, jnp.asarray(yd))
    ad = eng.activation_dispatch_for(plan, xd, capacity=capacity, slack=slack)
    if ad is None:
        return plan, None, None, None, None
    z_a, diag = dispatch_mod.execute_activation(
        ad, xd, yd, interpret=True, stats=eng.cache.stats)
    return plan, ad, np.asarray(z_a), diag, _reference(eng, plan, xd, yd)


# ------------------------------------------------------------ kernel level
@pytest.mark.parametrize("tm,tn,mkn,bd,eps,seed", [
    (32, 24, (90, 64, 44), 0.12, 0.0, 1),    # ragged rows, mixed primitives
    (32, 24, (90, 64, 44), 0.12, 0.1, 2),    # eps-thresholded packing
    (16, 8, (40, 32, 20), 0.50, 0.0, 3),     # ragged both axes
    (8, 16, (24, 16, 33), 0.40, 0.0, 4),     # ragged col tail
    (16, 8, (48, 32, 8), 0.05, 0.0, 5),      # nearly empty stripes (fillers)
])
def test_activation_route_bit_identical_to_eager_paths(tm, tn, mkn, bd,
                                                       eps, seed):
    M, K, N = mkn
    rng = np.random.default_rng(seed)
    xd = _block_sparse(rng, M, K, bd)
    yd = (rng.normal(size=(K, N)) *
          (rng.uniform(size=(K, N)) < 0.5)).astype(np.float32)
    eng = DynasparseEngine(tile_m=tm, tile_n=tn, literal=True, eps=eps)
    plan, ad, z_a, diag, z_p = _routes(eng, xd, yd)
    if ad is None:
        pytest.skip("plan routed no sparse tasks")
    assert not bool(diag["overflow"])
    np.testing.assert_array_equal(z_a, z_p)
    # the eager pass runs the same route on the same budget
    z_e = np.asarray(eng.execute(plan, xd, jnp.asarray(yd)))
    assert eng.last_route == ("act", ad)
    np.testing.assert_array_equal(z_e, z_a)
    if eps == 0.0:
        np.testing.assert_allclose(z_a, xd @ yd, rtol=1e-4, atol=1e-4)


def test_activation_route_skips_blocks():
    """The telemetry must show real skipping on a block-sparse activation:
    stored < logical, and the budget bounds the descriptor count."""
    rng = np.random.default_rng(11)
    xd = _block_sparse(rng, 96, 64, 0.25)
    yd = rng.normal(size=(64, 16)).astype(np.float32)
    eng = DynasparseEngine(tile_m=32, tile_n=8, literal=True)
    _, ad, z_a, diag, z_p = _routes(eng, xd, yd)
    assert ad is not None
    assert int(diag["stored"]) < int(diag["logical"])
    assert int(diag["stored"]) <= int(diag["capacity"])
    np.testing.assert_array_equal(z_a, z_p)


@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_activation_route_dtypes(dtype):
    if dtype == "bfloat16":
        import ml_dtypes
        dtype = ml_dtypes.bfloat16
    rng = np.random.default_rng(13)
    xd = _block_sparse(rng, 64, 32, 0.4, dtype=dtype)
    yd = rng.normal(size=(32, 16)).astype(np.float32)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True)
    _, ad, z_a, _, z_p = _routes(eng, xd, yd)
    if ad is None:
        pytest.skip("plan routed no sparse tasks")
    np.testing.assert_array_equal(z_a, z_p)


def test_capacity_exact_and_overflow_fallback():
    """capacity == exact need is bit-identical to eager; one slot below
    trips the overflow flag and yields the plain dense GEMM result INSIDE
    the same program (no error, no retrace)."""
    rng = np.random.default_rng(17)
    xd = _block_sparse(rng, 64, 48, 0.35)
    yd = rng.normal(size=(48, 16)).astype(np.float32)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True)
    plan = eng.plan(xd, jnp.asarray(yd))
    if not plan.stq:
        pytest.skip("plan routed no sparse tasks")
    need = dispatch_mod.activation_capacity(xd, plan.part, eng.block,
                                            slack=1.0)
    assert need is not None and need > 1

    _, ad, z_a, diag, z_p = _routes(eng, xd, yd, capacity=need)
    assert ad.geom.cap == need and not bool(diag["overflow"])
    np.testing.assert_array_equal(z_a, z_p)

    ad2 = eng.activation_dispatch_for(plan, xd, capacity=need - 1)
    z_o, diag2 = dispatch_mod.execute_activation(ad2, xd, yd, interpret=True)
    assert bool(diag2["overflow"])
    z_d = ops.gemm(jnp.asarray(xd), jnp.asarray(yd), interpret=True,
                   out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(z_o), np.asarray(z_d))
    np.testing.assert_allclose(np.asarray(z_o), xd @ yd,
                               rtol=1e-4, atol=1e-4)


def test_one_trace_serves_varying_sparsity_within_budget():
    """Shape stability: different activation sparsity patterns re-use ONE
    jitted trace (the whole point of the capacity parameterization), and
    the descriptors themselves are cache hits."""
    rng = np.random.default_rng(19)
    yd = rng.normal(size=(48, 16)).astype(np.float32)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True)
    xs = [_block_sparse(rng, 64, 48, bd) for bd in (0.30, 0.18, 0.05)]
    plan = eng.plan(xs[0], jnp.asarray(yd))
    if not plan.stq:
        pytest.skip("plan routed no sparse tasks")
    cap = dispatch_mod.activation_capacity(xs[0], plan.part, eng.block,
                                           slack=1.0)
    s = eng.cache.stats
    t0 = s.trace_builds
    # ONE dispatch — the warmup plan's — serves every later input, exactly
    # as a compiled whole-model program replays its recorded descriptors
    ad = eng.activation_dispatch_for(plan, xs[0], capacity=cap)
    assert ad is not None
    for xd in xs:
        z_a, diag = dispatch_mod.execute_activation(
            ad, xd, yd, interpret=True, stats=s)
        assert not bool(diag["overflow"])
        np.testing.assert_array_equal(np.asarray(z_a),
                                      _reference(eng, plan, xd, yd))
    assert s.trace_builds == t0 + 1      # ONE trace for all three patterns
    assert s.trace_cache_hits >= 2
    assert s.act_builds == 1


def test_descriptors_content_independent_across_activations():
    """Two different activations with one geometry/assignment must share
    one descriptor lowering (the act cache key has no content in it)."""
    rng = np.random.default_rng(23)
    yd = rng.normal(size=(32, 8)).astype(np.float32)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True)
    x1 = _block_sparse(rng, 48, 32, 0.15)
    # same pattern support, different values -> same densities/assignment
    x2 = (x1 * 1.7).astype(np.float32)
    p1 = eng.plan(x1, jnp.asarray(yd))
    if not p1.stq:
        pytest.skip("plan routed no sparse tasks")
    cap = dispatch_mod.activation_capacity(x1, p1.part, eng.block)
    a1 = eng.activation_dispatch_for(p1, x1, capacity=cap)
    p2 = eng.plan(x2, jnp.asarray(yd))
    a2 = eng.activation_dispatch_for(p2, x2, capacity=cap)
    assert a1 is not None and a1 is a2
    assert eng.cache.stats.act_builds == 1
    assert eng.cache.stats.act_hits == 1
    assert eng.cache.activation_count() == 1


def test_dense_plans_decline_activation_route():
    """A plan whose Analyzer routed everything to the dense engine must NOT
    take the block-skip route — dense wins, the kernel stays one GEMM."""
    rng = np.random.default_rng(29)
    xd = rng.normal(size=(64, 32)).astype(np.float32)      # fully dense
    yd = rng.normal(size=(32, 16)).astype(np.float32)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True)
    plan = eng.plan(xd, jnp.asarray(yd))
    if plan.stq:
        pytest.skip("analyzer unexpectedly routed sparse tasks")
    assert eng.activation_dispatch_for(plan, xd) is None
    # sparse X is dispatch_for's territory, never the activation route's
    adj = SparseCOO((64, 32), jnp.asarray([0]), jnp.asarray([0]),
                    jnp.asarray([1.0]), tag="adjacency")
    plan_adj = eng.plan(adj, jnp.asarray(yd))
    assert eng.activation_dispatch_for(plan_adj, adj) is None


# ------------------------------------------------------------- whole model
def _block_sparse_graph(rng, n=80, nnz=240):
    flat = np.sort(rng.choice(n * n, size=nnz, replace=False))
    return SparseCOO((n, n), jnp.asarray((flat // n).astype(np.int32)),
                     jnp.asarray((flat % n).astype(np.int32)),
                     jnp.asarray(np.abs(rng.normal(size=nnz)
                                        ).astype(np.float32)),
                     tag="adjacency")


def test_compile_model_uses_activation_route_and_matches():
    """Acceptance (ISSUE 5): a compiled whole-model program executes at
    least one activation-side kernel via the capacity block-skip route,
    matches the reference, and re-serves varying activation sparsity with
    zero retraces and zero overflows."""
    rng = np.random.default_rng(31)
    adj = _block_sparse_graph(rng)
    h = _block_sparse(rng, 80, 12, 0.35)
    params = gnn.init_params("GCN", 12, 8, 5)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True)
    warm, cm = gnn.compile_model("GCN", eng, adj, jnp.asarray(h), params)
    assert cm is not None
    assert cm.n_act >= 1, "no activation kernel took the block-skip route"
    ref = gnn.run_reference("GCN", adj, jnp.asarray(h), params)
    np.testing.assert_allclose(np.asarray(warm), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)

    z1 = cm(jnp.asarray(h))
    assert len(cm.last_activation) == cm.n_act
    assert all(not bool(d["overflow"]) for d in cm.last_activation)
    assert any(int(d["stored"]) < int(d["logical"])
               for d in cm.last_activation)
    np.testing.assert_allclose(np.asarray(z1), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)

    # sparser variant of the same support: same trace, still exact
    h2 = (h * (rng.uniform(size=h.shape) < 0.7)).astype(np.float32)
    z2 = cm(jnp.asarray(h2))
    assert cm.calls == 2 and cm.traces == 1
    ref2 = gnn.run_reference("GCN", adj, jnp.asarray(h2), params)
    np.testing.assert_allclose(np.asarray(z2), np.asarray(ref2),
                               rtol=1e-3, atol=1e-3)


def test_compile_model_activation_skip_off_keeps_dense_route():
    rng = np.random.default_rng(37)
    adj = _block_sparse_graph(rng)
    h = _block_sparse(rng, 80, 12, 0.35)
    params = gnn.init_params("GCN", 12, 8, 5)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True)
    warm, cm = gnn.compile_model("GCN", eng, adj, jnp.asarray(h), params,
                                 activation_skip=False)
    assert cm is not None and cm.n_act == 0
    z = cm(jnp.asarray(h))
    assert cm.last_activation == []
    ref = gnn.run_reference("GCN", adj, jnp.asarray(h), params)
    np.testing.assert_allclose(np.asarray(z), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_serving_steady_state_reports_skip_telemetry():
    """Post-warmup micro-batches must run compiled WITH the block-skip
    route active (skipped ratio > 0, zero overflows, zero replans) while
    activation sparsity varies within the capacity budget."""
    from repro.serving import ServingConfig, ServingEngine, SharedPlanCache

    rng = np.random.default_rng(41)
    adj = _block_sparse_graph(rng)
    params = gnn.init_params("GCN", 12, 8, 5)
    base = _block_sparse(rng, 80, 12, 0.35)
    batches = []
    for _ in range(12):
        jitter = (rng.uniform(size=base.shape) < 0.95)
        batches.append((base * jitter).astype(np.float32))

    cache = SharedPlanCache()
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True, cache=cache)
    with ServingEngine("GCN", params, engine=eng,
                       config=ServingConfig(max_batch=4)) as srv:
        srv.register_graph("g", adj)
        outs = srv.serve(("g", h) for h in batches)
    ref = gnn.run_reference("GCN", adj, jnp.asarray(batches[0]), params)
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)
    ds = srv.dispatch_stats()
    assert srv.stats.compiled_batches == srv.stats.batches - 1
    assert ds["replans"] == 0
    assert ds["act_kernels_last"] >= 1
    # steady-state compiled batches replay the cached activation
    # dispatches — the hit counter must reflect that reuse
    assert ds["act_hits"] > 0
    assert ds["act_overflows"] == 0
    assert ds["act_skipped_ratio_mean"] > 0.0
    assert len(srv.stats.activation_batches) == srv.stats.compiled_batches


# --------------------------------------------------- eager pack regression
def _pack_blockcsr_loop(x, block, *, capacity=None, eps=0.0):
    """The pre-ISSUE-5 per-block double loop — kept as the reference the
    vectorized ``pack_blockcsr`` must reproduce bit-for-bit."""
    x = np.asarray(x)
    M, K = x.shape
    B = block
    nrb, ncb = -(-M // B), -(-K // B)
    padded = np.zeros((nrb * B, ncb * B), dtype=x.dtype)
    padded[:M, :K] = x

    def _stored(blk):
        return np.any(blk != 0) if eps == 0.0 else np.any(np.abs(blk) > eps)

    rows, cols, first, blocks = [], [], [], []
    for rb in range(nrb):
        row_has = False
        for cb in range(ncb):
            blk = padded[rb * B:(rb + 1) * B, cb * B:(cb + 1) * B]
            if _stored(blk):
                rows.append(rb)
                cols.append(cb)
                first.append(0 if row_has else 1)
                blocks.append(blk)
                row_has = True
        if not row_has:
            rows.append(rb)
            cols.append(0)
            first.append(1)
            blocks.append(np.zeros((B, B), dtype=x.dtype))
    nnzb = len(blocks)
    cap = capacity if capacity is not None else nnzb
    for _ in range(cap - nnzb):
        rows.append(nrb - 1)
        cols.append(0)
        first.append(0)
        blocks.append(np.zeros((B, B), dtype=x.dtype))
    return BlockCSR((M, K), B, jnp.asarray(rows, dtype=jnp.int32),
                    jnp.asarray(cols, dtype=jnp.int32),
                    jnp.asarray(first, dtype=jnp.int32),
                    jnp.asarray(np.stack(blocks)), nnzb)


# ------------------------------------------- per-stripe capacity budgets
def _skewed_activation(rng, m=96, k=64, block=8):
    """One dense row-stripe, the rest nearly empty — the skew case where a
    uniform budget pads every stripe to the dense stripe's need."""
    x = np.zeros((m, k), np.float32)
    x[:16] = rng.normal(size=(16, k)).astype(np.float32)
    tail = _block_sparse(rng, m - 16, k, 0.06, block=block)
    x[16:] = tail
    return x


def test_pack_vector_capacity_uniform_is_bit_identical():
    """A per-stripe vector with every entry equal to the scalar budget must
    reproduce the historical uniform layout bit-for-bit."""
    rng = np.random.default_rng(51)
    x = _block_sparse(rng, 64, 32, 0.3)
    kw = dict(block=8, n_stripes=4, slot_rows=2, n_block_cols=4, eps=0.0)
    out_s = ops.pack_activation_stripes(x, capacity=5, **kw)
    out_v = ops.pack_activation_stripes(
        x, capacity=np.full(4, 5, np.int64), **kw)
    for a, b in zip(out_s, out_v):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pack_vector_capacity_trims_and_detects_overflow():
    rng = np.random.default_rng(53)
    x = _skewed_activation(rng)
    kw = dict(block=8, n_stripes=6, slot_rows=2, n_block_cols=8, eps=0.0)
    *_, nnzb, _real, ovf = ops.pack_activation_stripes(x, capacity=16, **kw)
    needs = np.asarray(nnzb)
    assert not bool(ovf)
    # exact per-stripe budgets: packed pool shrinks to sum(needs), no loss
    out = ops.pack_activation_stripes(x, capacity=needs, **kw)
    assert out[0].shape[0] == int(needs.sum()) < 6 * 16
    assert not bool(out[-1])
    # starving ONE stripe below its need must raise the overflow flag
    starved = needs.copy()
    starved[0] -= 1
    assert bool(ops.pack_activation_stripes(x, capacity=starved, **kw)[-1])


def test_per_stripe_budgets_cut_waste_bit_identically():
    """Acceptance (ISSUE 7 leg 2): on a skewed activation the per-stripe
    budget vector drops padded-slot waste ≥20% vs the uniform budget, with
    zero overflows and the identical (bitwise) compiled result."""
    rng = np.random.default_rng(57)
    xd = _skewed_activation(rng)
    yd = rng.normal(size=(64, 16)).astype(np.float32)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True)
    plan = eng.plan(xd, jnp.asarray(yd))
    ad_u = eng.activation_dispatch_for(plan, xd, per_stripe=False)
    ad_v = eng.activation_dispatch_for(plan, xd, per_stripe=True)
    if ad_u is None:
        pytest.skip("plan routed no sparse tasks")
    assert ad_u.geom.caps == () and ad_v.geom.caps != ()
    assert ad_v.geom.total_slots < ad_u.geom.total_slots

    z_u, diag_u = dispatch_mod.execute_activation(ad_u, xd, yd,
                                                  interpret=True)
    z_v, diag_v = dispatch_mod.execute_activation(ad_v, xd, yd,
                                                  interpret=True)
    assert not bool(diag_u["overflow"]) and not bool(diag_v["overflow"])
    np.testing.assert_array_equal(np.asarray(z_u), np.asarray(z_v))
    np.testing.assert_array_equal(np.asarray(z_v),
                                  _reference(eng, plan, xd, yd))

    stored = int(diag_v["stored"])
    waste_u = (int(diag_u["capacity"]) - stored) / max(stored, 1)
    waste_v = (int(diag_v["capacity"]) - stored) / max(stored, 1)
    assert waste_v <= 0.8 * waste_u, (waste_u, waste_v)


def test_per_stripe_budget_serves_jitter_without_overflow():
    """Jitter only removes elements from the warmup support, so each
    stripe's need can only shrink: the warmup-sized budget vector serves
    every jittered batch with zero overflows (and one shared descriptor
    build)."""
    rng = np.random.default_rng(59)
    xd = _skewed_activation(rng)
    yd = rng.normal(size=(64, 16)).astype(np.float32)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True)
    plan = eng.plan(xd, jnp.asarray(yd))
    ad = eng.activation_dispatch_for(plan, xd, per_stripe=True)
    if ad is None:
        pytest.skip("plan routed no sparse tasks")
    builds0 = eng.cache.stats.act_builds
    for i in range(4):
        xi = (xd * (rng.uniform(size=xd.shape) < 0.9)).astype(np.float32)
        z, diag = dispatch_mod.execute_activation(ad, xi, yd, interpret=True)
        assert not bool(diag["overflow"]), i
        np.testing.assert_array_equal(np.asarray(z),
                                      _reference(eng, plan, xi, yd))
        # same dispatch replayed — no rebuilds per batch
        assert eng.cache.stats.act_builds == builds0


# ------------------------------------------- steady-state act_hits credit
def test_compiled_model_credits_act_hits():
    """Regression (ISSUE 7 satellite): compiled steady-state calls replay
    the cached activation dispatches, so ``act_hits`` must grow past
    warmup — BENCH_dispatch.json used to read ``act_builds: 2, act_hits:
    0`` across 6 batches while every batch reused them."""
    rng = np.random.default_rng(61)
    adj = _block_sparse_graph(rng)
    h = _block_sparse(rng, 80, 12, 0.35)
    params = gnn.init_params("GCN", 12, 8, 5)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True)
    _, cm = gnn.compile_model("GCN", eng, adj, jnp.asarray(h), params)
    assert cm is not None and cm.n_act >= 1
    hits0 = eng.cache.stats.act_hits
    cm(jnp.asarray(h))
    cm(jnp.asarray(h))
    assert eng.cache.stats.act_hits == hits0 + 2 * cm.n_act
    assert eng.cache.stats.act_hits > 0


@pytest.mark.parametrize("seed", range(6))
def test_vectorized_pack_blockcsr_matches_loop(seed):
    rng = np.random.default_rng(seed)
    M, K = int(rng.integers(1, 45)), int(rng.integers(1, 45))
    B = int(rng.choice([4, 8]))
    eps = float(rng.choice([0.0, 0.1]))
    x = (rng.normal(size=(M, K)) *
         (rng.uniform(size=(M, K)) < rng.uniform(0, 0.6))).astype(np.float32)
    ref = _pack_blockcsr_loop(x, B, eps=eps)
    cap = ref.nnzb + int(rng.integers(0, 4))
    ref = _pack_blockcsr_loop(x, B, capacity=cap, eps=eps)
    got = pack_blockcsr(x, B, capacity=cap, eps=eps)
    assert got.nnzb == ref.nnzb
    for f in ("row_ids", "col_ids", "first", "blocks"):
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(ref, f)))


class _Device:
    """A device reporting ``bytes_limit`` of memory."""

    def __init__(self, limit):
        self.limit = limit

    def memory_stats(self):
        return {"bytes_limit": self.limit}


def test_block_pool_budget_is_half_the_device():
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True)
    assert eng.block_pool_fits(10**9)        # the CPU reports no limit
    eng.device = lambda: _Device(16 * 2**30)
    # GCN-CO's dense features (506,880 capacity slots) fit a 16 GiB chip;
    # FL's 5.63M slots of l1-update, or 1.43M of l2-update, do not
    assert eng.block_pool_fits(506_880)
    assert not eng.block_pool_fits(1_428_000)
    assert not eng.block_pool_fits(5_628_672)


def test_pool_past_the_device_runs_as_one_dense_gemm(monkeypatch):
    """A dense operand whose block pool would not fit the device takes the
    dense GEMM route, in the eager pass and in the compiled program, and
    still matches the reference."""
    rng = np.random.default_rng(41)
    adj = _block_sparse_graph(rng)
    h = _block_sparse(rng, 80, 12, 0.35)
    params = gnn.init_params("GCN", 12, 8, 5)
    ref = gnn.run_reference("GCN", adj, jnp.asarray(h), params)
    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True)
    _, cm = gnn.compile_model("GCN", eng, adj, jnp.asarray(h), params)
    act = [(name, r.n_stq) for (name, r), p in zip(cm.report.kernels,
                                                   cm.payload)
           if p is not None and "xd" not in p]
    assert act and all(n_stq for _, n_stq in act)
    small = DynasparseEngine(tile_m=16, tile_n=8, literal=True)
    small.device = lambda: _Device(2)

    def no_pool(*args, **kw):
        raise AssertionError("the eager pass packed a pool that cannot fit")
    monkeypatch.setattr(dispatch_mod, "execute_activation", no_pool)
    warm, cm = gnn.compile_model("GCN", small, adj, jnp.asarray(h), params)
    assert cm is not None and cm.n_act == 0
    # the same plans, with their sparse tasks, now lowered as dense GEMMs
    assert [(name, r.n_stq) for (name, r), p in zip(cm.report.kernels,
                                                    cm.payload)
            if p is None and r.n_stq] == act
    for z in (warm, cm(jnp.asarray(h))):
        np.testing.assert_allclose(np.asarray(z), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
