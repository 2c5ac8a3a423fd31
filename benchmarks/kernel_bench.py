"""Kernel microbenchmarks: the three Pallas primitives across density.

Wall-clock here is CPU interpret-mode (correctness path), NOT a TPU claim —
the TPU numbers are the perf-model / roofline terms also printed.  This bench
demonstrates the skip behaviour (SpDMM work scales with block density) and
that the PlanCache packs/analyzes a static adjacency exactly once across
layers and repeated inference calls.
"""
from __future__ import annotations

import dataclasses
import time

import jax.numpy as jnp
import numpy as np

from repro.core import DynasparseEngine, SparseCOO
from repro.core.perfmodel import TPUV5E, TaskShape, t_dense, t_spdmm
from repro.kernels import ops
from repro.kernels.formats import pack_blockcsr
from repro.models import gnn


def _time(fn, *args, reps=3, **kw):
    fn(*args, **kw)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        np.asarray(fn(*args, **kw))
    return (time.perf_counter() - t0) / reps


def run(csv: list[str]) -> None:
    print("\n== Kernel μbench (interpret-mode wall; v5e model time) ==")
    rng = np.random.default_rng(0)
    m = k = n = 256
    block = 32
    y = rng.normal(size=(k, n)).astype(np.float32)

    t_g = _time(ops.gemm, jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)),
                jnp.asarray(y), bm=64, bn=64, bk=64, interpret=True)
    model_t = t_dense(TaskShape(m, k, n, 1.0, 1.0), TPUV5E)
    print(f"gemm {m}x{k}x{n}: wall {t_g * 1e6:9.1f} us | v5e model "
          f"{model_t * 1e9:7.1f} ns")
    csv.append(f"kernel/gemm_{m},{t_g * 1e6:.1f},{model_t * 1e9:.1f}")

    for density in (0.1, 0.3, 0.6, 1.0):
        mask = (rng.uniform(size=(m // block, k // block)) < density
                ).astype(np.float32)
        a_dense = (rng.normal(size=(m, k)) *
                   np.kron(mask, np.ones((block, block)))).astype(np.float32)
        a = pack_blockcsr(a_dense, block)
        t_s = _time(ops.spdmm, a, jnp.asarray(y), bn=block, interpret=True)
        alpha = a.block_density()
        model_t = t_spdmm(TaskShape(m, k, n, alpha, 1.0), TPUV5E)
        print(f"spdmm α_blk={alpha:4.2f}: wall {t_s * 1e6:9.1f} us | "
              f"v5e model {model_t * 1e9:7.1f} ns | stored blocks "
              f"{a.stored_blocks}")
        csv.append(f"kernel/spdmm_a{alpha:.2f},{t_s * 1e6:.1f},"
                   f"{model_t * 1e9:.1f}")

    _run_plan_cache_demo(csv)


def _rand_adj(n: int, nnz: int, seed: int = 5) -> SparseCOO:
    rng = np.random.default_rng(seed)
    flat = np.sort(rng.choice(n * n, size=nnz, replace=False))
    return SparseCOO(
        (n, n),
        jnp.asarray((flat // n).astype(np.int32)),
        jnp.asarray((flat % n).astype(np.int32)),
        jnp.asarray(np.abs(rng.normal(size=nnz)).astype(np.float32)),
        tag="adjacency")


def _run_plan_cache_demo(csv: list[str]) -> None:
    """Plan cache on a 2-layer GCN (literal Pallas execution, interpret
    mode): the adjacency is packed and analyzed once across layers and
    repeated requests."""
    print("\n== PlanCache (2-layer GCN, literal) ==")
    rng = np.random.default_rng(0)
    n, f, hidden = 128, 24, 16
    adj = _rand_adj(n, 4 * n)
    h = jnp.asarray(rng.normal(size=(n, f)).astype(np.float32))
    params = gnn.init_params("GCN", f, hidden, hidden)

    # plan cache across layers and repeated requests
    eng = DynasparseEngine(tile_m=32, tile_n=8, literal=True)
    gnn.run_inference("GCN", eng, adj, h, params)
    s1 = dataclasses.replace(eng.cache.stats)   # snapshot: stats mutate in place
    print(f"inference 1: packs={s1.packs} analyzes={s1.analyzes} "
          f"plan hits={s1.plan_hits} misses={s1.plan_misses} "
          f"(layer-2 aggregation hits the layer-1 plan)")
    gnn.run_inference("GCN", eng, adj, h, params)
    s2 = dataclasses.replace(eng.cache.stats)
    print(f"inference 2: packs={s2.packs} analyzes={s2.analyzes} "
          f"plan hits={s2.plan_hits} misses={s2.plan_misses} "
          f"(serving path: every kernel replans nothing)")
    csv.append(f"plancache/packs,{s1.packs},{s2.packs}")
    csv.append(f"plancache/plan_hits,{s1.plan_hits},{s2.plan_hits}")
