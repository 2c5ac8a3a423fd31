"""Host-dispatch overhead benchmark: compiled dispatch vs eager rebuild.

``PYTHONPATH=src python benchmarks/dispatch_bench.py [--requests 48]
[--max-batch 8] [--out BENCH_dispatch.json] [--check]``

Measures the cost this PR removes from the serving steady state — the
per-request host work of re-deriving the fused-kernel instruction stream —
and gates that it stays removed:

1. **kernel_level** — one planned aggregation kernel: descriptor-lowering
   time (``build_dispatch``, the one-time cost), eager batched execute wall
   (per-request descriptor rebuild) vs compiled execute wall (one jitted
   call), and their bit-identity.
2. **serving_steady_state** — a request stream through the ServingEngine:
   per-request latency split into warmup (first batch: plan + pack + lower
   + trace) vs steady state p50/p99, plus the compiled-path counters.
3. **sparse_activation** — a block-sparse feature stream whose sparsity
   pattern varies per request: post-warmup batches must run compiled WITH
   the capacity block-skip route active (skipped-block ratio > 0, zero
   overflows) and zero retraces across the varying patterns.
4. **calibration** — the measured performance model (ISSUE 7): a fallback
   hardware model is calibrated against the real Pallas kernels once, the
   calibrated STQ/DTQ assignment's compiled execute is timed against the
   static-guess assignment on the same kernel, and a simulated restart
   (SharedPlanCache save/load) must replay the calibration with ZERO
   re-measures.
5. **per_stripe_budget** — skew-aware activation budgets (ISSUE 7 leg 2):
   on a skewed activation the per-stripe budget vector must cut padded-slot
   waste ≥20% vs the uniform budget, overflow-free, retrace-free and
   bit-identical to the eager path.
6. **multidev** — mesh-sharded compiled dispatch (ISSUE 8): row-stripe
   bands sharded over every visible device (the CI ``multidev`` lane forces
   8 host devices).  Bit-exact vs the eager executor of the same placed
   plan, one lowering, trace-free replay, per-shard descriptor streams of
   O(global / devices).
7. **halo** — owned+halo operand distribution (ISSUE 10): on a banded
   locality graph each device holds only its owned Y block-rows plus the
   thin halo its band reads, exchanged by a static ppermute schedule inside
   the compiled program.  Bit-exact vs the replicate-everything oracle and
   the eager executor; per-device dense-operand bytes strictly below the
   replicated baseline at >= 4 devices.

``--check`` (CI) enforces the ISSUE-4/5/7 acceptance criteria: in steady
state ``dispatch_builds == plans``, ``replans == 0``, every post-warmup
micro-batch runs compiled, the jit trace cache is hit on every micro-batch
after the first compiled one, the sparse-activation scenario keeps skipping
blocks without a single replan, retrace or capacity overflow (and its
steady-state act_hits grow), calibration replays from the cache with zero
re-measures while its assignment executes no slower than the static guess,
and the per-stripe budgets hit their waste-reduction bar.
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core import DynasparseEngine, SparseCOO
from repro.core import calibrate
from repro.core import dispatch as dispatch_mod
from repro.core.perfmodel import runtime_fallback
from repro.core.scheduler import execute_plan
from repro.models import gnn
from repro.serving import ServingConfig, ServingEngine, SharedPlanCache


def _fixed_graph(n: int = 128, avg_deg: int = 4, seed: int = 5) -> SparseCOO:
    rng = np.random.default_rng(seed)
    flat = np.sort(rng.choice(n * n, size=avg_deg * n, replace=False))
    return SparseCOO((n, n),
                     jnp.asarray((flat // n).astype(np.int32)),
                     jnp.asarray((flat % n).astype(np.int32)),
                     jnp.asarray(np.abs(rng.normal(size=avg_deg * n)
                                        ).astype(np.float32)),
                     tag="adjacency")


def _kernel_level(adj: SparseCOO, width: int = 16, repeats: int = 5) -> dict:
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.normal(size=(adj.shape[0], width)).astype(np.float32))
    eng = DynasparseEngine(tile_m=32, tile_n=8, literal=True,
                           cache=SharedPlanCache())
    plan = eng.plan(adj, y, name="agg")
    _, entry = eng._packed_structure(plan, adj)

    t0 = time.perf_counter()
    for _ in range(repeats):
        d = dispatch_mod.build_dispatch(plan.part, plan.stq, plan.dtq,
                                        entry.stripes, block=eng.block)
    build_s = (time.perf_counter() - t0) / repeats

    # eager batched: per-call descriptor rebuild (the pre-PR steady state)
    xd = None if not plan.dtq else jnp.asarray(adj.todense())
    t0 = time.perf_counter()
    for _ in range(repeats):
        z_e = execute_plan(plan.part, plan.stq, plan.dtq, xd, y,
                           block=eng.block, packed=entry.stripes)
        np.asarray(z_e)
    eager_s = (time.perf_counter() - t0) / repeats

    # compiled: warm the trace, then measure the steady-state call
    z_c = eng.execute(plan, adj, y)
    t0 = time.perf_counter()
    for _ in range(repeats):
        z_c = eng.execute(plan, adj, y)
        np.asarray(z_c)
    compiled_s = (time.perf_counter() - t0) / repeats

    return {
        "descriptor_build_s": build_s,
        "n_spdmm_entries": d.n_entries,
        "n_spmm_steps": d.n_spmm_steps,
        "eager_execute_s": eager_s,
        "compiled_execute_s": compiled_s,
        "speedup_eager_over_compiled": eager_s / max(compiled_s, 1e-12),
        "bit_identical": bool(np.array_equal(np.asarray(z_e),
                                             np.asarray(z_c))),
    }


def _serving_steady_state(adj: SparseCOO, requests: int, max_batch: int,
                          model: str, feat: int, hidden: int) -> dict:
    rng = np.random.default_rng(0)
    n = adj.shape[0]
    params = gnn.init_params(model, feat, hidden, hidden)
    batches = [rng.normal(size=(n, feat)).astype(np.float32)
               for _ in range(requests)]
    cache = SharedPlanCache()
    srv = ServingEngine(model, params,
                        engine=DynasparseEngine(tile_m=32, tile_n=8,
                                                literal=True, cache=cache),
                        config=ServingConfig(max_batch=max_batch))
    srv.register_graph("bench", adj)
    outs = srv.serve(("bench", h) for h in batches)

    ref = gnn.run_reference(model, adj, jnp.asarray(batches[0]), params)
    err = float(np.max(np.abs(np.asarray(outs[0]) - np.asarray(ref))))

    lat = sorted(r.latency for r in srv.stats.requests)
    warm = [r.latency for r in srv.stats.requests
            if r.request_id < max_batch]            # the warmup batch
    steady = [r.latency for r in srv.stats.requests
              if r.request_id >= max_batch]
    ds = srv.dispatch_stats()
    out = {
        "requests": requests,
        "batches": srv.stats.batches,
        "compiled_batches": srv.stats.compiled_batches,
        "warmup_latency_s": float(np.mean(warm)) if warm else 0.0,
        "steady_p50_s": float(np.percentile(steady, 50)) if steady else 0.0,
        "steady_p99_s": float(np.percentile(steady, 99)) if steady else 0.0,
        "latency_p50_s": float(np.percentile(lat, 50)),
        "max_abs_err_vs_reference": err,
        **ds,
    }
    srv.close()
    return out


def _sparse_activation(adj: SparseCOO, requests: int, max_batch: int,
                       model: str, feat: int, hidden: int) -> dict:
    """Block-sparse features with a per-request pattern wiggle: the compiled
    program must keep skipping activation blocks (ISSUE-5 tentpole) with
    zero retraces while the sparsity varies within the capacity budget."""
    rng = np.random.default_rng(3)
    n = adj.shape[0]
    params = gnn.init_params(model, feat, hidden, hidden)
    B = 8
    nrb, ncb = -(-n // B), -(-feat // B)
    mask = np.kron((rng.uniform(size=(nrb, ncb)) < 0.3).astype(np.float32),
                   np.ones((B, B)))[:n, :feat]
    batches = []
    for _ in range(requests):
        jitter = (rng.uniform(size=(n, feat)) < 0.95)
        batches.append((rng.normal(size=(n, feat)) * mask * jitter
                        ).astype(np.float32))
    cache = SharedPlanCache()
    srv = ServingEngine(model, params,
                        engine=DynasparseEngine(tile_m=32, tile_n=8,
                                                literal=True, cache=cache),
                        config=ServingConfig(max_batch=max_batch))
    srv.register_graph("bench", adj)
    outs = srv.serve(("bench", h) for h in batches)

    ref = gnn.run_reference(model, adj, jnp.asarray(batches[0]), params)
    err = float(np.max(np.abs(np.asarray(outs[0]) - np.asarray(ref))))
    ds = srv.dispatch_stats()
    act = srv.stats.activation_batches
    out = {
        "requests": requests,
        "batches": srv.stats.batches,
        "compiled_batches": srv.stats.compiled_batches,
        "compile_invalidations": srv.stats.compile_invalidations,
        "activation_batches": len(act),
        "max_abs_err_vs_reference": err,
        **ds,
    }
    srv.close()
    return out


def _calibration(adj: SparseCOO, width: int = 16, repeats: int = 9) -> dict:
    """Measured-model scenario (ISSUE 7 tentpole): calibrate the fallback
    model on the live backend, compare the calibrated STQ/DTQ assignment's
    compiled execute against the static-guess assignment on the SAME
    kernel, and prove a restarted process replays zero measurements."""
    rng = np.random.default_rng(1)
    y = jnp.asarray(rng.normal(size=(adj.shape[0], width)).astype(np.float32))
    base = runtime_fallback()
    cache = SharedPlanCache()
    eng_static = DynasparseEngine(base, tile_m=32, tile_n=8, literal=True,
                                  cache=cache, calibration="off")
    eng_cal = DynasparseEngine(base, tile_m=32, tile_n=8, literal=True,
                               cache=cache, calibration="auto")

    n0 = calibrate.measurement_count()
    t0 = time.perf_counter()
    plan_s = eng_static.plan(adj, y, name="agg")
    plan_c = eng_cal.plan(adj, y, name="agg")
    plan_s_total = time.perf_counter() - t0
    measured = calibrate.measurement_count() - n0
    hw = eng_cal.runtime_hw()

    def _timed(eng, plan):
        z = eng.execute(plan, adj, y)          # warm the trace
        np.asarray(z)
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            z = eng.execute(plan, adj, y)
            np.asarray(z)
            best = min(best, time.perf_counter() - t0)
        return best, z

    static_s, z_s = _timed(eng_static, plan_s)
    calib_s, z_c = _timed(eng_cal, plan_c)
    ref = np.asarray(adj.todense()) @ np.asarray(y)
    err = max(float(np.max(np.abs(np.asarray(z_s) - ref))),
              float(np.max(np.abs(np.asarray(z_c) - ref))))

    # simulated restart: a fresh cache loaded from the snapshot resolves
    # the calibrated model without touching a single kernel
    with tempfile.TemporaryDirectory() as td:
        snap = os.path.join(td, "cache.pkl")
        cache.save(snap)
        fresh = SharedPlanCache()
        fresh.load(snap)
        n1 = calibrate.measurement_count()
        eng_restart = DynasparseEngine(base, tile_m=32, tile_n=8,
                                       literal=True, cache=fresh,
                                       calibration="auto")
        restored = eng_restart.runtime_hw()
        re_measures = calibrate.measurement_count() - n1
        replay = {
            "re_measures_after_restart": re_measures,
            "restart_calib_builds": fresh.stats.calib_builds,
            "restart_calib_hits": fresh.stats.calib_hits,
            "model_restored": bool(restored == hw),
        }

    return {
        "backend": compat.backend_kind(),
        "base_model": base.name,
        "calibrated_model": hw.name,
        # CI caches the snapshot file: warm runs legitimately measure 0
        "snapshot_env_set": bool(os.environ.get(calibrate.SNAPSHOT_ENV)),
        "measurements": measured,
        "n_samples": hw.n_samples,
        "fit_residual": hw.fit_residual,
        "gemm_s_per_mac": hw.gemm_s_per_mac,
        "spdmm_s_per_mac": hw.spdmm_s_per_mac,
        "spmm_s_per_mac": hw.spmm_s_per_mac,
        "dispatch_overhead_s": hw.dispatch_overhead,
        "mem_bw_bytes_s": hw.mem_bw,
        "roofline_bw_ratio": hw.roofline_bw_ratio,
        "plan_and_calibrate_s": plan_s_total,
        "static_n_stq": len(plan_s.stq),
        "static_n_dtq": len(plan_s.dtq),
        "calibrated_n_stq": len(plan_c.stq),
        "calibrated_n_dtq": len(plan_c.dtq),
        "assignment_differs": ([t.queue for t in plan_s.part.tasks]
                               != [t.queue for t in plan_c.part.tasks]),
        "static_execute_s": static_s,
        "calibrated_execute_s": calib_s,
        "max_abs_err_vs_reference": err,
        "calib_builds": cache.stats.calib_builds,
        "calib_hits": cache.stats.calib_hits,
        **replay,
    }


def _per_stripe_budget(repeats: int = 4) -> dict:
    """Skew-aware budget scenario (ISSUE 7 leg 2): one dense row-stripe,
    the rest nearly empty.  The uniform budget pads every stripe to the
    dense one's need; the per-stripe vector pays each stripe its own."""
    rng = np.random.default_rng(7)
    m, k, width = 96, 64, 16
    x = np.zeros((m, k), np.float32)
    x[:16] = rng.normal(size=(16, k)).astype(np.float32)
    B = 8
    nrb, ncb = (m - 16) // B, k // B
    mask = np.kron((rng.uniform(size=(nrb, ncb)) < 0.06).astype(np.float32),
                   np.ones((B, B)))
    x[16:] = (rng.normal(size=(m - 16, k)) * mask).astype(np.float32)
    y = rng.normal(size=(k, width)).astype(np.float32)

    eng = DynasparseEngine(tile_m=16, tile_n=8, literal=True,
                           cache=SharedPlanCache())
    plan = eng.plan(x, jnp.asarray(y), name="act")
    ad_u = eng.activation_dispatch_for(plan, x, per_stripe=False)
    ad_v = eng.activation_dispatch_for(plan, x, per_stripe=True)
    if ad_u is None or ad_v is None:
        return {"skipped": "plan routed no sparse tasks"}
    stats = eng.cache.stats

    def _run(ad):
        # warmup batch (x itself) then jittered batches: the single trace
        # must serve all of them, budget never overflowing
        tb0 = stats.trace_builds
        z0, diag0 = dispatch_mod.execute_activation(
            ad, x, y, interpret=True, stats=stats)
        overflows = int(bool(diag0["overflow"]))
        for keep in rng.uniform(size=(repeats, m, k)) < 0.9:
            xi = (x * keep).astype(np.float32)
            _, diag = dispatch_mod.execute_activation(
                ad, xi, y, interpret=True, stats=stats)
            overflows += int(bool(diag["overflow"]))
        return np.asarray(z0), diag0, overflows, stats.trace_builds - tb0

    z_u, diag_u, ovf_u, traces_u = _run(ad_u)
    z_v, diag_v, ovf_v, traces_v = _run(ad_v)
    z_eager = np.asarray(execute_plan(plan.part, plan.stq, plan.dtq,
                                      x, y, batched=True, eps=eng.eps))

    stored = int(diag_v["stored"])          # same warmup x on both routes
    logical = int(diag_v["logical"])
    cap_u, cap_v = int(diag_u["capacity"]), int(diag_v["capacity"])
    waste_u = (cap_u - stored) / max(logical, 1)
    waste_v = (cap_v - stored) / max(logical, 1)
    return {
        "uniform_slots": ad_u.geom.total_slots,
        "per_stripe_slots": ad_v.geom.total_slots,
        "budgets": list(map(int, ad_v.geom.cap_vec)),
        "stored_blocks": stored,
        "logical_blocks": logical,
        "padded_waste_uniform": waste_u,
        "padded_waste_per_stripe": waste_v,
        "waste_reduction": 1.0 - waste_v / max(waste_u, 1e-12),
        "overflows": ovf_u + ovf_v,
        # one trace per route, every jittered batch replayed trace-free
        "retraces": max(0, traces_u - 1) + max(0, traces_v - 1),
        "bit_identical_to_eager": bool(
            np.array_equal(z_u, z_eager) and np.array_equal(z_v, z_eager)),
    }


def _multidev(adj: SparseCOO, width: int = 16, repeats: int = 5) -> dict:
    """Mesh-sharded dispatch scenario (ISSUE 8): the engine shards the
    row-stripe bands over every visible device (1 in the default lane, 8 in
    the CI ``multidev`` lane via XLA_FLAGS).  The sharded compiled execute
    must be bit-exact vs the eager executor of the SAME placed plan, lower
    the plan exactly once, replay trace-free, and each shard must carry
    O(descriptors / device) — not the global stream."""
    import jax

    from repro.launch.mesh import make_data_mesh

    nd = len(jax.devices())
    rng = np.random.default_rng(2)
    y = jnp.asarray(rng.normal(size=(adj.shape[0], width)).astype(np.float32))
    cache = SharedPlanCache()
    eng = DynasparseEngine(tile_m=32, tile_n=8, literal=True, cache=cache,
                           mesh=make_data_mesh(nd))
    plan = eng.plan(adj, y, name="agg")
    _, entry = eng._packed_structure(plan, adj)

    # eager executor of the SAME placed plan — the bit-identity oracle
    xd = None if not plan.dtq else jnp.asarray(adj.todense())
    z_e = execute_plan(plan.part, plan.stq, plan.dtq, xd, y,
                       block=eng.block, batched=True, packed=entry.stripes,
                       eps=eng.eps)

    z_c = eng.execute(plan, adj, y)          # warm: lower + trace once
    tb0 = cache.stats.trace_builds
    t0 = time.perf_counter()
    for _ in range(repeats):
        z_c = eng.execute(plan, adj, y)
        np.asarray(z_c)
    compiled_s = (time.perf_counter() - t0) / repeats
    retraces = cache.stats.trace_builds - tb0

    # per-shard instruction stream vs the global single-device stream
    sd = eng.sharded_dispatch_for(plan, adj)
    per_dev = 0
    for k in ("sp_a_ids", "mm_a_ids", "gemm_rows"):
        if k in sd.arrays:
            per_dev += int(sd.arrays[k].shape[-1])
    d_global = dispatch_mod.build_dispatch(plan.part, plan.stq, plan.dtq,
                                           entry.stripes, block=eng.block)
    global_desc = d_global.sparse_steps
    if "gemm_rows" in d_global.arrays:
        global_desc += int(d_global.arrays["gemm_rows"].shape[-1])

    return {
        "n_devices": nd,
        "band_sizes": list(plan.placement.band_sizes()),
        "per_device_descriptors": per_dev,
        "global_descriptors": global_desc,
        "sharded_dispatches": cache.sharded_count(),
        "dispatch_builds": cache.stats.dispatch_builds,
        "dispatch_hits": cache.stats.dispatch_hits,
        "retraces_after_warmup": retraces,
        "compiled_execute_s": compiled_s,
        "bit_identical_to_eager": bool(np.array_equal(np.asarray(z_e),
                                                      np.asarray(z_c))),
    }


def _halo(width: int = 16, repeats: int = 5) -> dict:
    """Owned+halo operand scenario (ISSUE 10): a banded locality graph
    (every edge within a fixed row distance) sharded over every visible
    device with ``operand_sharding="halo"`` against the
    replicate-everything oracle and the eager executor of the same placed
    plan.  Gates: bitwise identity both ways, exactly one lowering replayed
    trace-free, and — once there are >= 4 devices — per-device dense-operand
    residency strictly below the replicated baseline (each device holds its
    own row blocks plus a thin halo, not all of Y)."""
    import jax

    from repro.launch.mesh import make_data_mesh

    nd = len(jax.devices())
    # banded graph: |row - col| < 24 keeps most referenced Y rows inside
    # the owning band, so the halo is genuinely thin
    n, deg, bwidth = 256, 6, 24
    rng = np.random.default_rng(4)
    rows = np.sort(rng.integers(0, n, deg * n)).astype(np.int32)
    offs = rng.integers(-bwidth, bwidth + 1, deg * n)
    cols = np.clip(rows + offs, 0, n - 1).astype(np.int32)
    vals = np.abs(rng.normal(size=deg * n)).astype(np.float32)
    adj = SparseCOO((n, n), jnp.asarray(rows), jnp.asarray(cols),
                    jnp.asarray(vals), tag="adjacency")
    y = jnp.asarray(rng.normal(size=(n, width)).astype(np.float32))

    mesh = make_data_mesh(nd)
    cache = SharedPlanCache()
    eng_h = DynasparseEngine(tile_m=32, tile_n=8, literal=True, cache=cache,
                             mesh=mesh)                    # halo default
    eng_r = DynasparseEngine(tile_m=32, tile_n=8, literal=True,
                             cache=SharedPlanCache(), mesh=mesh,
                             operand_sharding="replicate")
    plan = eng_h.plan(adj, y, name="agg")
    _, entry = eng_h._packed_structure(plan, adj)

    xd = None if not plan.dtq else jnp.asarray(adj.todense())
    z_e = execute_plan(plan.part, plan.stq, plan.dtq, xd, y,
                       block=eng_h.block, batched=True,
                       packed=entry.stripes, eps=eng_h.eps)
    z_r = eng_r.execute(eng_r.plan(adj, y, name="agg"), adj, y)

    z_h = eng_h.execute(plan, adj, y)         # warm: lower + trace once
    tb0 = cache.stats.trace_builds
    t0 = time.perf_counter()
    for _ in range(repeats):
        z_h = eng_h.execute(plan, adj, y)
        np.asarray(z_h)
    compiled_s = (time.perf_counter() - t0) / repeats
    retraces = cache.stats.trace_builds - tb0

    sd = eng_h.sharded_dispatch_for(plan, adj)
    ob = sd.operand_bytes
    return {
        "n_devices": nd,
        "graph_vertices": n,
        "graph_bandwidth_rows": bwidth,
        "band_sizes": list(plan.placement.band_sizes()),
        "halo_blocks_total": sum(len(cs.halo) for cs in sd.supports),
        "exchange_rounds": int(sd.halo.n_rounds) if sd.halo else 0,
        "owned_bytes": ob["owned_bytes"],
        "halo_bytes": ob["halo_bytes"],
        "fallback_bytes": ob["fallback_bytes"],
        "per_device_bytes_halo": ob["halo_per_device_bytes"],
        "per_device_bytes_replicated": ob["replicated_per_device_bytes"],
        "halo_bytes_ratio": (ob["halo_per_device_bytes"]
                             / max(ob["replicated_per_device_bytes"], 1)),
        "sharded_dispatches": cache.sharded_count(),
        "retraces_after_warmup": retraces,
        "compiled_execute_s": compiled_s,
        "bit_identical_to_replicated": bool(
            np.array_equal(np.asarray(z_h), np.asarray(z_r))),
        "bit_identical_to_eager": bool(
            np.array_equal(np.asarray(z_h), np.asarray(z_e))),
    }


def run(requests: int = 48, max_batch: int = 8, model: str = "GCN",
        feat: int = 24, hidden: int = 16) -> dict:
    adj = _fixed_graph()
    return {
        "model": model,
        "graph_vertices": adj.shape[0],
        "max_batch": max_batch,
        "kernel_level": _kernel_level(adj),
        "serving_steady_state": _serving_steady_state(
            adj, requests, max_batch, model, feat, hidden),
        "sparse_activation": _sparse_activation(
            adj, requests, max_batch, model, feat, hidden),
        "calibration": _calibration(adj),
        "per_stripe_budget": _per_stripe_budget(),
        "multidev": _multidev(adj),
        "halo": _halo(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--model", default="GCN")
    ap.add_argument("--out", default="BENCH_dispatch.json")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless the steady state is fully "
                         "compiled: dispatch_builds == plans, replans == 0, "
                         "every post-warmup batch compiled + trace-cache hit")
    args = ap.parse_args()

    res = run(requests=args.requests, max_batch=args.max_batch,
              model=args.model)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"[dispatch_bench] wrote {args.out}")
    print(json.dumps(res, indent=2))
    if args.check:
        k = res["kernel_level"]
        s = res["serving_steady_state"]
        a = res["sparse_activation"]
        ok = (k["bit_identical"]
              and s["max_abs_err_vs_reference"] < 1e-3
              # every plan was lowered exactly once; nothing re-derived
              and s["dispatch_builds"] == s["plans"]
              and s["replans"] == 0
              # every batch after the warmup ran as one compiled call...
              and s["compiled_batches"] == s["batches"] - 1
              # ...and every compiled batch after the first hit the trace
              and s["trace_cache_hits"] >= s["compiled_batches"] - 1
              and s["trace_cache_hits"] > 0)
        # sparse-activation route (ISSUE 5): post-warmup batches keep the
        # block-skip active across varying patterns — no replans, no
        # retraces (the single warmup trace serves every batch), no
        # capacity overflows, and a real skipped-block ratio
        ok = (ok
              and a["max_abs_err_vs_reference"] < 1e-3
              and a["compiled_batches"] == a["batches"] - 1
              and a["activation_batches"] == a["compiled_batches"]
              and a["act_kernels_last"] >= 1
              and a["act_skipped_ratio_mean"] > 0.0
              and a["act_overflows"] == 0
              and a["replans"] == 0
              and a["compile_invalidations"] == 0
              and a["trace_cache_hits"] >= a["compiled_batches"] - 1
              # steady-state calls must CREDIT the cached act dispatches
              and a["act_hits"] > 0)
        # calibration (ISSUE 7 tentpole): the model was actually measured
        # (unless replayed from a CI-cached snapshot), the calibrated
        # assignment's compiled execute is no slower than the static guess,
        # and a restarted process replays with ZERO re-measures
        c = res["calibration"]
        ok = (ok
              and (c["measurements"] > 0 or c["snapshot_env_set"])
              and c["max_abs_err_vs_reference"] < 1e-3
              # noise guard: min-of-9 on a ~2 ms kernel still jitters
              and c["calibrated_execute_s"]
                  <= c["static_execute_s"] * 1.10 + 3e-4
              and c["re_measures_after_restart"] == 0
              and c["restart_calib_builds"] == 0
              and c["restart_calib_hits"] == 1
              and c["model_restored"])
        # per-stripe budgets (ISSUE 7 leg 2): ≥20% less padded-slot waste
        # than the uniform budget, overflow-free, retrace-free, bit-exact
        p = res["per_stripe_budget"]
        ok = (ok
              and "skipped" not in p
              and p["padded_waste_per_stripe"]
                  <= 0.8 * p["padded_waste_uniform"]
              and p["overflows"] == 0
              and p["retraces"] == 0
              and p["bit_identical_to_eager"])
        # mesh-sharded dispatch (ISSUE 8): bit-exact vs the eager executor
        # of the same placed plan, exactly one lowering replayed trace-free
        # on every later call, and each shard carries O(descriptors/device)
        # — strictly fewer than the global stream once there are >= 4 bands
        m = res["multidev"]
        ok = (ok
              and m["bit_identical_to_eager"]
              and m["sharded_dispatches"] == 1
              and m["retraces_after_warmup"] == 0
              and m["dispatch_hits"] > 0
              and sum(m["band_sizes"]) > 0
              and (m["n_devices"] < 4
                   or m["per_device_descriptors"]
                       < m["global_descriptors"]))
        # owned+halo operands (ISSUE 10): bit-exact vs BOTH the replicated
        # oracle and the eager executor, one lowering replayed trace-free,
        # and per-device dense-operand residency strictly sublinear (the
        # memory headline) once there are >= 4 devices — at 1 device the
        # owned+halo buffer plus the input slab legitimately exceeds one
        # replicated copy
        h = res["halo"]
        ok = (ok
              and h["bit_identical_to_replicated"]
              and h["bit_identical_to_eager"]
              and h["sharded_dispatches"] == 1
              and h["retraces_after_warmup"] == 0
              and (h["n_devices"] < 4 or h["halo_bytes_ratio"] < 1.0))
        if not ok:
            raise SystemExit("[dispatch_bench] acceptance check FAILED")
        print("[dispatch_bench] acceptance check passed")


if __name__ == "__main__":
    main()
