"""GNN model zoo of the paper: GCN, GraphSAGE, GIN, SGC.

Every model is expressed against an abstract matmul ``mm(x, y, name)`` so the
same definition runs (a) through the DynasparseEngine (paper's accelerator),
(b) as a pure-jnp reference for tests.  2-layer configurations per §IV-B:
hidden 16 for CO/CI/PU, 128 for FL/NE/RE.

Kernel ordering follows Dynasparse: aggregation ``Â·X`` and transformation
``X·W`` are separate kernels; for GCN/SGC/SAGE we use the FLOPs-optimal
association (transform-first when in_dim > out_dim; a transform-first SAGE
layer runs its root and neighbour transforms as one kernel) — GIN's
``(1+ε)h + Â·h`` pins aggregation to the raw features, which is why GIN
keeps a higher aggregation cost (visible in Table VI).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import dispatch as _dispatch
from repro.core import shard_exec as _shard_exec
from repro.core import sparsity
from repro.core.engine import (ACTIVATION_SLACK, DynasparseEngine,
                               EngineReport)
from repro.core.primitives import SparseCOO
from repro.kernels import ops

MM = Callable[..., jax.Array]   # mm(x, y, name=...) -> z

MODELS = ("GCN", "GraphSAGE", "GIN", "SGC")


def _glorot(rng: np.random.Generator, m: int, n: int) -> jnp.ndarray:
    s = np.sqrt(2.0 / (m + n))
    return jnp.asarray(rng.normal(0, s, size=(m, n)).astype(np.float32))


def init_params(model: str, in_dim: int, hidden: int, out_dim: int,
                seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    if model == "GCN":
        return {"W1": _glorot(rng, in_dim, hidden),
                "W2": _glorot(rng, hidden, out_dim)}
    if model == "GraphSAGE":
        return {"Ws1": _glorot(rng, in_dim, hidden),
                "Wn1": _glorot(rng, in_dim, hidden),
                "Ws2": _glorot(rng, hidden, out_dim),
                "Wn2": _glorot(rng, hidden, out_dim)}
    if model == "GIN":
        return {"M1a": _glorot(rng, in_dim, hidden),
                "M1b": _glorot(rng, hidden, hidden),
                "M2a": _glorot(rng, hidden, hidden),
                "M2b": _glorot(rng, hidden, out_dim)}
    if model == "SGC":
        return {"W1": _glorot(rng, in_dim, hidden),
                "W2": _glorot(rng, hidden, out_dim)}
    raise ValueError(model)


def _transform_then_aggregate(mm: MM, adj, h, w, tag: str):
    """Â·(h·W) vs (Â·h)·W by FLOPs; both orders routed through ``mm``."""
    in_dim, out_dim = w.shape
    if in_dim >= out_dim:
        z = mm(h, w, name=f"{tag}-update")
        return mm(adj, z, name=f"{tag}-agg")
    z = mm(adj, h, name=f"{tag}-agg")
    return mm(z, w, name=f"{tag}-update")


def gcn_apply(mm: MM, adj, h, p) -> jax.Array:
    z = jax.nn.relu(_transform_then_aggregate(mm, adj, h, p["W1"], "l1"))
    return _transform_then_aggregate(mm, adj, z, p["W2"], "l2")


def _sage_layer(mm: MM, adj, h, w_self, w_neigh, tag: str):
    """One GraphSAGE layer, ``W · CONCAT(h_v, AGG(h_N(v)))`` (Hamilton et
    al., arXiv:1706.02216, Alg. 1), which by the linearity of aggregation is
    ``h·W_self + Â·(h·W_neigh)``.

    Where the transform goes first, both halves are ONE transform
    ``h·[W_self | W_neigh]`` whose neighbour half is then aggregated: the
    input is read, laid out and packed once.  Each request's ``2d``-wide
    column block of the (possibly stacked) output is ``[self | neigh]``, so
    the split is a reshape to ``(N, k, 2, d)``; it and the sum run under the
    transform's name scope.  Where the aggregation goes first, the root
    transform stays a kernel of its own (``-self``)."""
    in_dim, out_dim = w_neigh.shape
    if in_dim < out_dim:
        return (mm(h, w_self, name=f"{tag}-self")
                + _transform_then_aggregate(mm, adj, h, w_neigh, tag))
    name = f"{tag}-update"
    z = mm(h, jnp.concatenate([w_self, w_neigh], axis=1), name=name)
    with jax.named_scope(name):
        z = z.reshape(z.shape[0], -1, 2, out_dim)
        z_self = z[:, :, 0].reshape(z.shape[0], -1)
        z_neigh = z[:, :, 1].reshape(z.shape[0], -1)
    z_agg = mm(adj, z_neigh, name=f"{tag}-agg")
    with jax.named_scope(name):
        return z_self + z_agg


def sage_apply(mm: MM, adj, h, p) -> jax.Array:
    z = jax.nn.relu(_sage_layer(mm, adj, h, p["Ws1"], p["Wn1"], "l1"))
    return _sage_layer(mm, adj, z, p["Ws2"], p["Wn2"], "l2")


def gin_apply(mm: MM, adj, h, p, eps: float = 0.0) -> jax.Array:
    # aggregation is pinned to raw features: (1+ε)h + Â·h
    def dense(x):
        return jnp.asarray(x.todense()) if isinstance(x, SparseCOO) else x

    a1 = mm(adj, h, name="l1-agg")
    z = (1.0 + eps) * dense(h) + a1
    z = jax.nn.relu(mm(z, p["M1a"], name="l1-mlp1"))
    z = jax.nn.relu(mm(z, p["M1b"], name="l1-mlp2"))
    a2 = mm(adj, z, name="l2-agg")
    z = (1.0 + eps) * z + a2
    z = jax.nn.relu(mm(z, p["M2a"], name="l2-mlp1"))
    return mm(z, p["M2b"], name="l2-mlp2")


def sgc_apply(mm: MM, adj, h, p) -> jax.Array:
    # SGC: Â^2 · X · W1 · W2, no nonlinearity — optimal order transforms first
    z = mm(h, p["W1"], name="update1")
    z = mm(z, p["W2"], name="update2")
    z = mm(adj, z, name="agg1")
    return mm(adj, z, name="agg2")


APPLY = {"GCN": gcn_apply, "GraphSAGE": sage_apply, "GIN": gin_apply,
         "SGC": sgc_apply}


# ---------------------------------------------------------------- runners
def engine_mm(engine: DynasparseEngine) -> MM:
    def mm(x, y, name="kernel"):
        z, _ = engine.matmul(x, y, name=name)
        return z
    return mm


def reference_mm(x, y, name="kernel"):
    if isinstance(x, SparseCOO):
        x = jnp.asarray(x.todense())
    if isinstance(y, SparseCOO):
        y = jnp.asarray(y.todense())
    return jnp.dot(x, y, preferred_element_type=jnp.float32)


@dataclasses.dataclass
class CompiledModel:
    """A whole model's kernel sequence fused into ONE jitted program.

    The GraphAGILE property at model scope: after one eager warmup pass has
    planned/packed/lowered every kernel, a steady-state micro-batch is a
    single compiled call — no Python per-kernel dispatch, no descriptor
    work, no per-kernel launches from the host's point of view.

    ``report`` is the warmup pass's :class:`EngineReport`; the schedule
    reports are plan-time simulations, so every later call on the same
    geometry would reproduce them verbatim — :meth:`fresh_report` hands the
    serving layer an identical (shallow) copy per batch.  Each call also
    credits ``plan_hits`` for its sparse kernels on ``stats``: a compiled
    call IS the reuse of those cached plans, and the hit-rate signal should
    keep reflecting that amortization.
    """
    model: str
    run: Callable                 # jitted replay: run(payload, h)
                                  #   -> (logits, activation diags)
    payload: list                 # per-kernel descriptor/pool pytrees
    report: EngineReport          # warmup report template (plan simulations)
    input_sketch: np.ndarray      # col-density sketch of the warmup features
    sketch_tile: int
    n_kernels: int
    n_sparse: int
    n_act: int = 0                # kernels on the capacity block-skip route
    # grid steps a call walks in its adjacency kernels' sparse sections, and
    # how many of those sections walk whole output rows
    sparse_steps: int = 0
    row_walk_sections: int = 0
    stats: object | None = None   # CacheStats receiving call accounting
    faults: object | None = None  # FaultInjector probed at "compiled"
    calls: int = 0
    traces: int = 0               # distinct input signatures (jit retraces)
    # per-activation-kernel telemetry of the LAST call: stored/capacity/
    # logical block counts + overflow flag (device scalars; see
    # repro.core.dispatch.apply_activation_dispatch)
    last_activation: list = dataclasses.field(default_factory=list)
    _seen: set = dataclasses.field(default_factory=set)

    def drifted(self, h, threshold: float, *, max_rows: int = 256,
                eps: float = 0.0) -> bool:
        """Has the input's column density drifted past ``threshold`` from
        the features this program was compiled against?  The compiled path
        cannot sketch intermediate activations (they only exist inside the
        jitted program), so the input sketch is the invalidation signal —
        on drift the caller re-runs the eager path, whose per-kernel
        sketches replan stale assignments, and recompiles."""
        sk = sparsity.sketch_col_density(jnp.asarray(h), self.sketch_tile,
                                         max_rows=max_rows, eps=eps)
        return sparsity.density_drift(sk, self.input_sketch) > threshold

    def fresh_report(self) -> EngineReport:
        return EngineReport(kernels=list(self.report.kernels),
                            meta=list(self.report.meta))

    def __call__(self, h) -> jax.Array:
        # the whole-model compiled-execute site: a fault here exercises the
        # serving layer's compiled -> eager degradation ladder (the probe
        # runs BEFORE any stats are credited, so a failed call never skews
        # the steady-state hit accounting)
        if self.faults is not None:
            self.faults.probe("compiled", detail=self.model)
        h = jnp.asarray(h)
        sig = (tuple(h.shape), str(h.dtype))
        new = sig not in self._seen
        self._seen.add(sig)
        self.calls += 1
        self.traces += int(new)
        if self.stats is not None:
            if new:
                self.stats.trace_builds += 1
            else:
                self.stats.trace_cache_hits += 1
            self.stats.plan_hits += self.n_sparse
            # a compiled call equally replays the cached ActivationDispatch
            # descriptors of its block-skip kernels — credit act_hits so the
            # steady-state hit rate reflects that reuse (the builds happened
            # at warmup; without this the counter read "2 builds, 0 hits"
            # forever while every batch reused them)
            self.stats.act_hits += self.n_act
        logits, self.last_activation = self.run(self.payload, h)
        return logits


def compile_model(model: str, engine: DynasparseEngine, adj, h, params,
                  *, transport=None, activation_skip: bool = True,
                  activation_slack: float = ACTIVATION_SLACK,
                  activation_per_stripe: bool = True):
    """Fuse all layer kernels of (model, graph, feature shape) into a single
    jitted program; returns ``(warmup logits, CompiledModel | None)``.

    The warmup is ONE ordinary eager pass through ``engine.matmul`` — it
    plans, packs and lowers every kernel into the plan cache and runs each
    through :meth:`DynasparseEngine.route`, while this function records the
    route (``sparse``, ``shard``, ``act`` or ``gemm``) and its descriptors.
    The replay then re-traces the model with every kernel inlined as its
    route's body, the whole sequence under ONE ``jax.jit``.

    An activation-side (dense X) kernel whose warmup plan routed tasks to
    the sparse engine is inlined as the capacity-padded block-skip route
    (:class:`~repro.core.dispatch.ActivationDispatch` — zero blocks of the
    intermediate features are skipped with FIXED shapes, budgeted at
    ``activation_slack`` headroom over the warmup's stored blocks — per
    stripe when ``activation_per_stripe`` (default), so skewed activations
    don't pad every stripe to the densest one's need; a batch that
    overflows the budget falls back to a dense GEMM inside the same
    program, never a retrace).  When the Analyzer sent everything to the
    dense engine — dense activations win — or the pool would not fit the
    device, the kernel stays one dense Pallas GEMM.
    ``activation_skip=False`` forces the dense-GEMM route for every
    activation kernel of the program; the warmup pass itself runs the
    engine's default routes.

    ``None`` (second element) for a non-literal engine, which runs plain
    ``jax.numpy`` and has no kernels to record.

    ``transport`` optionally wraps the abstract ``mm`` with a representation
    transform (the serving layer's column-stack/row-unstack transport) and
    must be trace-pure.
    """
    transport = transport if transport is not None else (lambda mm: mm)
    h = jnp.asarray(h)
    if not engine.literal:
        return APPLY[model](transport(engine_mm(engine)), adj, h, params), None
    # the warmup's own routes serve the program unless its activation
    # options differ from the eager pass's (``DynasparseEngine.route``'s
    # defaults)
    options = dict(activation_skip=activation_skip, slack=activation_slack,
                   per_stripe=activation_per_stripe)
    same_as_eager = options == dict(activation_skip=True,
                                    slack=ACTIVATION_SLACK, per_stripe=True)
    # ("sparse", geom) | ("shard", (geom, band_rows, halo)) | ("act", geom)
    # | ("gemm", None) per kernel
    records: list[tuple[str, object]] = []
    payload: list = []
    lowered: list = []             # each adjacency kernel's dispatch
    n0 = len(engine.report.kernels)

    def recording(x, y, name="kernel"):
        z, _ = engine.matmul(x, y, name=name)
        kind, d = engine.last_route
        if kind in ("act", "gemm") and not same_as_eager:
            kind, d = engine.route(engine.last_plan, x, **options)
        if kind == "gemm":
            records.append((kind, None))
            payload.append(None)
        elif kind == "act":
            records.append((kind, d.geom))
            payload.append({"arrays": dict(d.arrays)})
        else:
            lowered.append(d)
            records.append((kind, (d.geom, d.band_rows, d.halo)
                            if kind == "shard" else d.geom))
            payload.append({"arrays": dict(d.arrays),
                            "xd": engine.dense_x(engine.last_plan, x, d)})
        return z

    logits = APPLY[model](transport(recording), adj, h, params)

    def replay(payload_, hh):
        # resolved when the program is traced, for the backend it targets
        interpret = (ops.default_interpret() if engine.interpret is None
                     else engine.interpret)
        ctr = itertools.count()
        act_diags = []

        def unsharded(f, *args):
            # a mesh program cannot partition a Mosaic kernel on its own: a
            # kernel the plan does not shard runs whole on every device
            if engine.mesh is None:
                return f(*args)
            return compat.shard_map(f, mesh=engine.mesh,
                                    in_specs=tuple(P() for _ in args),
                                    out_specs=P())(*args)

        def mm(x, y, name="kernel"):
            i = next(ctr)
            kind, geom = records[i]
            if kind == "gemm":
                return unsharded(
                    lambda xx, yy: ops.gemm(xx, yy, interpret=interpret,
                                            out_dtype=jnp.float32),
                    jnp.asarray(x), jnp.asarray(y))
            p = payload_[i]
            if kind == "act":
                z, diag = unsharded(
                    lambda arrays, xx, yy: _dispatch.apply_activation_dispatch(
                        geom, arrays, xx, yy, interpret=interpret),
                    p["arrays"], jnp.asarray(x), jnp.asarray(y))
                act_diags.append(diag)
                return z
            if kind == "shard":
                sgeom, band_rows, halo = geom
                return _shard_exec.apply_sharded(
                    sgeom, band_rows, p["arrays"], p["xd"], y,
                    mesh=engine.mesh, interpret=interpret, halo=halo)
            return _dispatch.apply_dispatch(geom, p["arrays"], p["xd"], y,
                                            interpret=interpret)

        kernel_mm = transport(mm)

        def scoped(x, y, name="kernel"):
            # the kernel's name on every op it lowers to, its transport's
            # reshapes and transposes among them
            with jax.named_scope(name):
                return kernel_mm(x, y, name=name)

        out = APPLY[model](scoped, adj, hh, params)
        return out, act_diags

    tn = engine.tile_n or min(128, int(h.shape[1]))
    sketch = sparsity.sketch_col_density(h, tn, max_rows=engine.sketch_rows,
                                         eps=engine.eps)
    report = EngineReport(kernels=list(engine.report.kernels[n0:]),
                          meta=list(engine.report.meta[n0:]))
    return logits, CompiledModel(
        model=model, run=jax.jit(replay), payload=payload, report=report,
        input_sketch=np.asarray(sketch), sketch_tile=tn,
        n_kernels=len(records),
        n_sparse=sum(1 for k, _ in records if k in ("sparse", "shard")),
        n_act=sum(1 for k, _ in records if k == "act"),
        sparse_steps=sum(d.sparse_steps for d in lowered),
        row_walk_sections=sum(d.row_walk_sections for d in lowered),
        stats=engine.cache.stats, faults=engine.faults)


def run_inference(model: str, engine: DynasparseEngine, adj, h, params):
    """Full-graph inference through the accelerator; returns logits and the
    engine report accumulated across all kernels.

    ``engine.reset()`` clears only the report — the engine's plan cache
    survives, so the adjacency's stripe densities, task assignment and packed
    BlockCSR stripes are computed on the first call and reused by every layer
    and every subsequent call on the same graph."""
    engine.reset()
    logits = APPLY[model](engine_mm(engine), adj, h, params)
    return logits, engine.report


def run_serving(model: str, engine: DynasparseEngine, adj, feature_batches,
                params, *, max_batch: int = 1):
    """Serving path: repeated inference over a stream of feature matrices on
    a FIXED graph — a thin wrapper over :mod:`repro.serving`.

    Request 1 populates the engine's plan cache; every later request hits it
    (no density re-measurement, no re-analysis, no re-packing), and the
    density sketch revalidates each hit against the live feature batch.
    ``max_batch > 1`` additionally coalesces the stream into micro-batches
    served with one plan/execute pass each.  Returns (list of logits, list
    of per-request engine reports — each the request's 1/k share of its
    micro-batch report; the raw batch reports live on the serving engine's
    ``stats.batch_reports``)."""
    from repro.serving import ServingConfig, ServingEngine

    with ServingEngine(model, params, engine=engine,
                       config=ServingConfig(max_batch=max_batch)) as srv:
        srv.register_graph("default", adj)
        outs = srv.serve(("default", jnp.asarray(h)) for h in feature_batches)
        by_id = sorted(srv.stats.requests, key=lambda r: r.request_id)
        return outs, [r.report for r in by_id]


def run_reference(model: str, adj, h, params):
    """The plain float32 ``jax.numpy`` oracle, every product at
    ``Precision.HIGHEST`` (a TPU's default is bfloat16 passes)."""
    with jax.default_matmul_precision("highest"):
        return APPLY[model](reference_mm, adj, h, params)
