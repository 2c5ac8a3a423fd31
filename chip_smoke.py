"""On-chip smoke test of the GCN serving path on TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the halo-sharded four-chip phase only

Serves GCN on CO at its Table IV size (2,708 vertices, 5,429 edges, 1,433
input features, hidden width 16, 7 classes) through the entry points a user
calls: ``load_graph`` → ``gnn.init_params`` → ``DynasparseEngine`` (real
Pallas kernels, planned against the chip's own hardware model) →
``ServingEngine.serve`` at ``max_batch=8``.  The first micro-batch is the
eager warmup; every later one must run the compiled whole-model program.
Every request's logits are checked against a float64 NumPy reference.

With ``--chips 4`` the same graph is served with ``ServingConfig(
n_devices=4)`` (row-stripe bands on a 4-device mesh, halo exchange), and
every batch's logits are compared bitwise with the single-device executor
run on the same plans.

Everything runs in this one process; it starts no other.  The script exits
non-zero, printing no result, when JAX finds no TPU; any failed phase or
check raises.  The last line of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Times printed on the way are informational; none of them is a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

MODEL = "GCN"
DATASET = "CO"
MAX_BATCH = 8
# Max |logit - reference| allowed.  The logits are O(1) (max |ref| ~ 0.7).
# float32 end to end differs from the float64 reference by ~5e-8 (measured
# with XLA's CPU backend); a single bfloat16 MXU pass per matmul would
# differ by ~2e-3.  1e-5 passes the first with a wide margin and fails the
# second: the kernels run their float32 dots at Precision.HIGHEST.
TOL = 1e-5


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _requests(h0, n: int, seed: int):
    """``n`` seeded requests: the dataset's features with small noise on
    their nonzeros (the sparsity pattern, hence the plan, stays put)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    mask = h0 != 0
    return [(h0 + rng.normal(0, 0.01, size=h0.shape).astype(np.float32)
             * mask).astype(np.float32) for _ in range(n)]


def _reference(adj, params):
    """Independent float64 NumPy GCN: ``Â · relu(Â · H · W1) · W2``."""
    import numpy as np
    n = adj.shape[0]
    a = np.zeros((n, n), np.float64)
    np.add.at(a, (np.asarray(adj.rows), np.asarray(adj.cols)),
              np.asarray(adj.vals, np.float64))
    w1 = np.asarray(params["W1"], np.float64)
    w2 = np.asarray(params["W2"], np.float64)
    return lambda h: a @ (np.maximum(a @ (np.asarray(h, np.float64) @ w1),
                                     0.0) @ w2)


def _max_err(outs, hs, ref) -> float:
    import numpy as np
    return max(float(np.max(np.abs(np.asarray(z, np.float64) - ref(h))))
               for z, h in zip(outs, hs))


class _CompileClock:
    """Sums the backend compile seconds jax reports while it is open."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self._event = "/jax/core/compile/backend_compile_duration"
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self._event:
            self.seconds += duration


def _check(checks: dict) -> None:
    for name, ok in checks.items():
        _log(f"check {name}: {'pass' if ok else 'FAIL'}")
    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        raise SystemExit(f"chip_smoke: failed checks: {failed}")


def _setup(seed: int):
    from repro.data.graphs import load_graph
    from repro.models import gnn

    g = load_graph(DATASET, scale=1.0)
    st = g.stats
    h0 = __import__("numpy").asarray(g.features_dense)
    _log(f"graph {DATASET}: vertices={st.vertices} edges={st.edges} "
         f"features={h0.shape[1]} hidden={st.hidden} classes={st.classes} "
         f"adjacency nnz={g.adj.nnz}")
    if (st.vertices, st.edges, h0.shape[1], st.hidden, st.classes) != (
            2708, 5429, 1433, 16, 7):
        raise SystemExit("chip_smoke: CO is not at its Table IV size")
    params = gnn.init_params(MODEL, h0.shape[1], st.hidden, st.classes,
                             seed=seed)
    return g, h0, params


def _engine(mesh=None):
    from repro.core import DynasparseEngine, calibrate
    from repro.core.perfmodel import runtime_fallback
    from repro.serving import SharedPlanCache

    engine = DynasparseEngine(runtime_fallback(), literal=True,
                              calibration="auto", cache=SharedPlanCache(),
                              mesh=mesh)
    t0 = time.perf_counter()
    hw = engine.runtime_hw()
    snap = os.environ.get(calibrate.SNAPSHOT_ENV)
    _log(f"hardware model: {hw.name} (fallback base {engine.hw.name})")
    _log(f"calibration provenance: calibrated={hw.calibrated} "
         f"samples={getattr(hw, 'n_samples', 0)} "
         f"measured_in_process={calibrate.measurement_count()} "
         f"snapshot={snap or 'none'} "
         f"fit_residual={getattr(hw, 'fit_residual', 0.0):.3g} "
         f"seconds={time.perf_counter() - t0:.1f} (informational)")
    if not hw.calibrated:
        raise SystemExit("chip_smoke: the runtime model is not calibrated")
    return engine


def one_chip(args) -> None:
    from repro.kernels import ops
    from repro.serving import ServingConfig, ServingEngine

    g, h0, params = _setup(args.seed)
    ref = _reference(g.adj, params)
    clock = _CompileClock()
    engine = _engine()
    srv = ServingEngine(MODEL, params, engine=engine,
                        config=ServingConfig(max_batch=MAX_BATCH))
    srv.register_graph(DATASET, g.adj)
    hs = _requests(h0, args.requests, args.seed + 1)

    try:
        # the warmup micro-batch on its own, so its launches are countable
        ops.reset_pallas_call_count()
        outs = srv.serve((DATASET, h) for h in hs[:MAX_BATCH])
        warmup_launches = ops.pallas_call_count()
        outs += srv.serve((DATASET, h) for h in hs[MAX_BATCH:])
    finally:
        srv.close()

    err = _max_err(outs, hs, ref)
    stats = srv.stats.as_dict()
    d = srv.dispatch_stats()
    _log(f"compile seconds: {clock.seconds:.1f}")
    _log(f"pallas launches in warmup: {warmup_launches}")
    _log(f"max abs error vs float64 reference: {err:.3g} (tolerance {TOL})")
    _log("serving stats: " + json.dumps(stats))
    _log("dispatch stats: " + json.dumps(
        {k: v for k, v in d.items() if k != "health"}))
    _log(f"latency p50: {stats['latency']['p50']:.4f} s (informational, "
         f"includes warmup and compilation)")
    _check({
        "requests served": len(outs) == args.requests,
        "errors == 0": stats["errors"] == 0,
        "quarantined == 0": stats["quarantined"] == 0,
        "degraded_batches == 0": stats["degraded_batches"] == 0,
        "compiled_batches == batches - 1": (
            stats["compiled_batches"] == stats["batches"] - 1),
        "replans == 0": d["replans"] == 0,
        "dispatch_builds == plans": d["dispatch_builds"] == d["plans"],
        "pallas launches in warmup > 0": warmup_launches > 0,
        "interpret mode off": ops.default_interpret() is False,
        f"max abs error <= {TOL}": err <= TOL,
    })


def four_chips(args) -> None:
    import jax
    import numpy as np

    from repro.core import DynasparseEngine
    from repro.launch.mesh import make_data_mesh
    from repro.models import gnn
    from repro.serving import ServingConfig, ServingEngine
    from repro.serving.engine import stacked_transport

    g, h0, params = _setup(args.seed)
    ref = _reference(g.adj, params)
    clock = _CompileClock()
    engine = _engine(mesh=make_data_mesh(4))
    # the plan each kernel was given at warmup: the compiled program runs
    # exactly these, so the oracle replays them on one device
    plans = {}
    plan = engine.plan

    def recording_plan(x, y, name="kernel"):
        p = plan(x, y, name=name)
        plans.setdefault(name, p)
        return p

    engine.plan = recording_plan
    srv = ServingEngine(MODEL, params, engine=engine,
                        config=ServingConfig(max_batch=MAX_BATCH, n_devices=4))
    srv.register_graph(DATASET, g.adj)
    hs = _requests(h0, args.requests, args.seed + 1)
    try:
        outs = srv.serve((DATASET, h) for h in hs)
    finally:
        srv.close()

    # the single-device executor: a literal engine without a mesh runs
    # each plan through its own routes (the compiled dispatch for the
    # adjacency, which the halo path is held bit-equal to)
    single = DynasparseEngine(literal=True, block=engine.block,
                              eps=engine.eps)

    def oracle_mm(x, y, name="kernel"):
        return single.execute(plans[name], x, y)

    bitwise = []
    for b in range(0, len(hs), MAX_BATCH):
        stacked = np.concatenate(hs[b:b + MAX_BATCH], axis=1)
        want = np.asarray(gnn.APPLY[MODEL](stacked_transport(oracle_mm),
                                           g.adj, stacked, params))
        got = np.concatenate([np.asarray(z) for z in outs[b:b + MAX_BATCH]],
                             axis=1)
        bitwise.append(bool((got == want).all()))

    sharded = [engine.sharded_dispatch_for(p, g.adj)
               for p in plans.values() if p.struct_key is not None]
    spans = sorted({len(a.sharding.device_set)
                    for sd in sharded for a in sd.arrays.values()})
    err = _max_err(outs, hs, ref)
    stats = srv.stats.as_dict()
    d = srv.dispatch_stats()
    _log(f"compile seconds: {clock.seconds:.1f}")
    _log(f"bitwise equal to the single-device executor, per batch: {bitwise}")
    _log(f"sharded dispatches: {len(sharded)}; devices spanned by their "
         f"arrays: {spans}")
    _log(f"max abs error vs float64 reference: {err:.3g} (tolerance {TOL})")
    _log("serving stats: " + json.dumps(stats))
    _log("dispatch stats: " + json.dumps(
        {k: v for k, v in d.items() if k != "health"}))
    _check({
        "requests served": len(outs) == args.requests,
        "errors == 0": stats["errors"] == 0,
        "degraded_batches == 0": stats["degraded_batches"] == 0,
        "compiled_batches == batches - 1": (
            stats["compiled_batches"] == stats["batches"] - 1),
        "halo operand sharding": d["operand_sharding"] == "halo",
        "logits bitwise equal to the single-device executor": all(bitwise),
        "sharded arrays span 4 devices": bool(sharded) and spans == [4],
        f"max abs error <= {TOL}": err <= TOL,
        "mesh of 4": d["n_devices"] == 4 == len(jax.devices()),
    })


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--requests", type=int, default=32,
                    help="requests to serve (full micro-batches of 8)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found "
                 f"{jax.default_backend()!r}")
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    _log(f"device: platform={devices[0].platform} "
         f"kind={devices[0].device_kind!r} count={len(devices)}")
    _log(f"compile cache: {enable_compile_cache()}")
    if args.requests < 4 * MAX_BATCH or args.requests % MAX_BATCH:
        sys.exit(f"chip_smoke: --requests must be a multiple of {MAX_BATCH}"
                 f" and at least {4 * MAX_BATCH}")
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args)
    _log(f"wall seconds: {time.perf_counter() - t0:.1f} (informational)")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
