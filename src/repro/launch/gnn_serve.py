"""GNN serving driver: async micro-batched inference over a shared cache.

``PYTHONPATH=src python -m repro.launch.gnn_serve --dataset CO --model GCN
[--requests 64] [--max-batch 8] [--scale 1.0] [--cache-file plan.pkl]``

Fires a burst of synthetic same-graph requests through the ServingEngine
and prints a machine-readable stats line: latency percentiles, micro-batch
sizes, plan-cache hit rate and the compiled path's dispatch counters.
Every kernel runs as a Pallas kernel — compiled for the chip on TPU, in
interpret mode on any other backend — and the runtime mapping plans against
the hardware model of the device in use (``perfmodel.runtime_fallback``, calibrated on
first plan).  With ``--cache-file`` the SharedPlanCache is loaded before
serving (restart skips re-analysis — observe packs/analyzes stay 0) and
saved after.
"""
from __future__ import annotations

import argparse
import json
import os


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="CO", help="Table-IV dataset id")
    ap.add_argument("--model", default="GCN")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-delay-ms", type=float, default=0.0)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="graph scale factor (1.0 = Table IV size)")
    ap.add_argument("--drift-threshold", type=float, default=0.25)
    ap.add_argument("--cache-file", default=None,
                    help="load the shared plan cache before serving, save "
                         "after (serving-restart persistence)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    import numpy as np

    from repro.core import DynasparseEngine
    from repro.core.perfmodel import runtime_fallback
    from repro.data.graphs import load_graph
    from repro.models import gnn
    from repro.serving import (ServingConfig, ServingEngine, SharedPlanCache,
                               SketchConfig)

    g = load_graph(args.dataset, scale=args.scale)
    in_dim = (g.features.shape[1] if hasattr(g.features, "shape")
              else g.stats.features)
    params = gnn.init_params(args.model, in_dim, g.stats.hidden,
                             g.stats.classes)

    cache = SharedPlanCache()
    if args.cache_file and os.path.exists(args.cache_file):
        print(f"[gnn_serve] loaded cache: {cache.load(args.cache_file)}")
    engine = DynasparseEngine(runtime_fallback(), literal=True,
                              calibration="auto", cache=cache)
    srv = ServingEngine(
        args.model, params, engine=engine,
        config=ServingConfig(
            max_batch=args.max_batch,
            max_delay_s=args.max_delay_ms * 1e-3,
            sketch=SketchConfig(threshold=args.drift_threshold)))
    srv.register_graph(args.dataset, g.adj)

    rng = np.random.default_rng(0)
    h0 = np.asarray(g.features_dense)
    reqs = []
    for _ in range(args.requests):
        noise = rng.normal(0, 0.01, size=h0.shape).astype(np.float32)
        reqs.append((args.dataset, (h0 + noise * (h0 != 0)).astype(np.float32)))

    try:
        srv.serve(reqs)
    finally:
        srv.close()

    stats = srv.stats.as_dict()
    stats.update({
        "dataset": args.dataset, "model": args.model,
        "vertices": g.stats.vertices,
        "hardware_model": engine.runtime_hw().name,
        "cache": cache.stats.as_dict(),
        "cache_bytes": cache.bytes_used,
        "plan_hit_rate": cache.stats.hit_rate,
        "dispatch": srv.dispatch_stats(),
    })
    print("[gnn_serve] " + json.dumps(stats))

    if args.cache_file:
        print(f"[gnn_serve] saved cache: {cache.save(args.cache_file)}")
    return stats


if __name__ == "__main__":
    main()
