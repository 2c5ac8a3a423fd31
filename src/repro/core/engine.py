"""DynasparseEngine — the paper's accelerator as a composable JAX module.

One engine instance owns: the hardware model (VCK5000 for paper-fidelity
numbers, TPUv5e for deployment decisions), the 2-D partitioning geometry, the
Analyzer, the Scheduler and a structure-keyed :class:`PlanCache`.  Every GNN
kernel (and any other matmul routed through it, e.g. MoE expert dispatch)
goes through::

    z, report = engine.matmul(x, y, name="agg-l1")

which splits into two phases:

- ``plan``: (1) measure stripe densities, (2) build the task grid, (3) run
  the Analyzer (STQ/DTQ assignment via the perf model), (4) simulate the
  Scheduler for the hardware-time estimate.  For a ``SparseCOO`` operand the
  whole phase is cached on the sparsity structure — layer 2 and every
  subsequent inference request reuse the layer-1 plan (the paper's Alg. 4
  preprocessing amortized across layers, Dynasparse-style).

- ``execute``: compute the result.  A ``literal=True`` engine runs the
  kernel through :meth:`DynasparseEngine.route` — the same four routes
  (``shard``, ``sparse``, ``act``, ``gemm``) the whole-model compiled
  program records, each one jitted call on descriptors served from the
  cache; otherwise plain ``jax.numpy``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import analyzer as _analyzer
from repro.core import dispatch as _dispatch
from repro.core import primitives as prim
from repro.core import scheduler as _scheduler
from repro.core import shard_exec as _shard_exec
from repro.core import sparsity
from repro.kernels import ops as _ops
from repro.core.partition import choose_tile, make_tasks
from repro.core.perfmodel import VCK5000, HardwareModel
from repro.core.plancache import (KernelPlan, PlanCache, StructureEntry,
                                  coo_fingerprint)
from repro.core.primitives import SparseCOO
from repro.kernels.formats import pack_blockcsr_coo

Mode = Literal["dynamic", "sparse_only", "dense_only"]

# HBM bytes of one stored block of a block-skip pool on a TPU: the (B, B)
# float32 block, B = 8, sits in one (8, 128) tile, 16x the bytes it holds.
# The route holds two such arrays at once (the packed payload and the pool
# a kernel reads): compiled for a v5e, GCN-CO's 506,880 capacity slots take
# 4.16 GB of temporaries.
_POOL_BYTES_PER_BLOCK = 2 * 8 * 128 * 4

# headroom of an activation kernel's stored-block budget over its warmup
# need: the eager pass's route, and the compiled program's by default
ACTIVATION_SLACK = 1.5


@dataclasses.dataclass
class EngineReport:
    """Accumulated per-kernel schedule reports (one inference run)."""
    kernels: list[tuple[str, _scheduler.ScheduleReport]] = dataclasses.field(
        default_factory=list)
    # per-kernel recording used by the benchmark harness to replay the same
    # kernel sequence at full-scale geometry (see benchmarks/common.py)
    meta: list[dict] = dataclasses.field(default_factory=list)

    @property
    def total(self) -> _scheduler.ScheduleReport:
        if not self.kernels:
            return _scheduler.ScheduleReport.zero()
        rep = self.kernels[0][1]
        for _, r in self.kernels[1:]:
            rep = rep.merge(r)
        return rep

    @property
    def hardware_time(self) -> float:
        """End-to-end hardware execution time (kernels are sequential across
        layers — layer l+1 depends on layer l — but each kernel overlaps its
        two queues internally)."""
        return sum(r.makespan for _, r in self.kernels)

    def attributed(self, k: int) -> "EngineReport":
        """An even per-request share of a micro-batch report: every kernel's
        cost fields are divided by ``k`` (the batch's request count), so
        ``hardware_time``/FLOPs sum back to the batch total across its
        requests.  The kernel list and task counts still describe the shared
        fused launches.  ``k <= 1`` returns ``self`` — a batch of one IS the
        request."""
        if k <= 1:
            return self
        s = 1.0 / k
        return EngineReport(
            kernels=[(name, rep.scaled(s)) for name, rep in self.kernels],
            meta=list(self.meta))

    @property
    def by_device(self) -> list[_scheduler.ScheduleReport]:
        """Per-device totals of a (possibly) sharded run — one merged
        :class:`ScheduleReport` per mesh device, so heterogeneous device
        times are not silently summed into one scalar.  Kernels without a
        per-device breakdown (unsharded plans) are attributed to device 0;
        an unsharded run therefore returns ``[self.total]``."""
        out: list[_scheduler.ScheduleReport] = []
        for _, rep in self.kernels:
            per = list(rep.per_device) if rep.per_device else [rep]
            while len(out) < len(per):
                out.append(_scheduler.ScheduleReport.zero())
            for d, r in enumerate(per):
                out[d] = out[d].merge(r)
        return out


class DynasparseEngine:
    def __init__(
        self,
        hw: HardwareModel = VCK5000,
        *,
        tile_m: int | None = None,
        tile_n: int | None = None,
        mode: Mode = "dynamic",
        strategy: str = "balanced",
        literal: bool = False,
        block: int = 8,
        interpret: bool | None = None,
        eps: float = 0.0,
        cache: PlanCache | None = None,
        drift_threshold: float | None = None,
        sketch_rows: int = 256,
        calibration: object = "auto",
        mesh: object = None,
        operand_sharding: str = "halo",
        per_device_models: "list[HardwareModel] | None" = None,
        faults: object = None,
    ):
        self.hw = hw
        # optional repro.serving.faults.FaultInjector (duck-typed: anything
        # with .probe(site, detail)) consulted at the instrumented sites —
        # plan / lower / pack / execute and, on mesh engines, the sharded
        # path's shard_lower / shard_exec.  None (the default) keeps every
        # probe a no-op; the serving layer threads its configured injector
        # through here so chaos scenarios exercise the engine's real paths.
        self.faults = faults
        # 1-D ("data",) jax mesh → sharded plan/compile/execute: the
        # Analyzer's STQ/DTQ split becomes a two-level (device, queue)
        # placement and compiled kernels run under shard_map, one banded
        # program per device.  None = classic single-device engine (and a
        # size-1 mesh is the degenerate case of the SAME sharded path).
        if mesh is not None:
            names = tuple(getattr(mesh, "axis_names", ()))
            if names != ("data",):
                raise ValueError(
                    f"DynasparseEngine mesh must be 1-D with axis ('data',), "
                    f"got axes {names!r}")
        self.mesh = mesh
        # dense-operand distribution of the sharded executor: "halo" (the
        # default) ships each device only its OWNED block-rows plus the
        # halo its band reads (ppermute exchange inside the program);
        # "replicate" keeps the PR 8 full-replication layout — the bitwise
        # correctness oracle the halo path is gated against.
        if operand_sharding not in _shard_exec.OPERAND_SHARDINGS:
            raise ValueError(
                f"operand_sharding must be one of "
                f"{_shard_exec.OPERAND_SHARDINGS}, got {operand_sharding!r}")
        self.operand_sharding = operand_sharding
        # heterogeneous per-device cost models for band placement: the
        # band_partition DP already takes per-(device, stripe) costs, this
        # hook feeds it genuinely different models (e.g. two calibrated
        # device generations) instead of n_devices copies of ``hw``.
        if per_device_models is not None:
            if mesh is None:
                raise ValueError(
                    "per_device_models requires a mesh engine")
            n_mesh = int(np.prod(mesh.devices.shape))
            if len(per_device_models) != n_mesh:
                raise ValueError(
                    f"per_device_models must list one model per mesh device "
                    f"({n_mesh}), got {len(per_device_models)}")
            per_device_models = list(per_device_models)
        self.per_device_models = per_device_models
        # "auto": hw models marked ``fallback=True`` are replaced for
        # ANALYSIS by a measured CalibratedModel on first plan (lazy — the
        # sweep runs once per process and persists through self.cache);
        # "off": trust hw as given; a HardwareModel instance: use it.
        # Analytical models (VCK5000 & friends) are never calibrated away —
        # they reproduce the paper's tables by design.
        self.calibration = calibration
        self._hw_runtime: HardwareModel | None = None
        self.tile_m = tile_m
        self.tile_n = tile_n
        self.mode = mode
        self.strategy = strategy
        self.literal = literal
        self.block = block
        self.interpret = interpret
        self.eps = eps
        self.cache = PlanCache() if cache is None else cache
        # density-drift revalidation of plan hits (the serving subsystem
        # enables this; None keeps the raw first-call amortization)
        self.drift_threshold = drift_threshold
        self.sketch_rows = sketch_rows
        self.report = EngineReport()
        # the plan behind the most recent matmul/plan call — lets the
        # whole-model compiler (models.gnn.compile_model) record each
        # kernel's plan without re-entering the cache/sketch machinery
        self.last_plan: KernelPlan | None = None
        # the (kind, dispatch) route the most recent literal execute took
        self.last_route: tuple[str, object] | None = None

    @property
    def n_devices(self) -> int:
        """Mesh size (1 for classic single-device engines)."""
        return 1 if self.mesh is None else int(np.prod(self.mesh.devices.shape))

    def reset(self) -> None:
        """Clear the accumulated report.  The plan cache survives — it is
        keyed on operand structure, not on the inference run (serving path)."""
        self.report = EngineReport()

    # ------------------------------------------------------------------
    def runtime_hw(self) -> HardwareModel:
        """The model the Analyzer/Scheduler actually consult.

        Resolved once per engine: an explicit ``calibration`` model wins;
        ``"auto"`` calibrates ``fallback=True`` models through
        ``repro.core.calibrate`` (cache-first — a warm ``PlanCache`` or
        ``$REPRO_CALIBRATION_PATH`` snapshot means zero measurements) and
        leaves analytical models untouched; anything else keeps ``hw``.
        """
        if self._hw_runtime is None:
            hw = self.hw
            if isinstance(self.calibration, HardwareModel):
                hw = self.calibration
            elif self.calibration == "auto" and self.hw.fallback:
                from repro.core import calibrate as _calibrate
                hw = _calibrate.get_calibrated(
                    self.cache, self.hw, block=self.block,
                    interpret=self.interpret)
            self._hw_runtime = hw
        return self._hw_runtime

    def device(self):
        """The device an unsharded kernel runs on."""
        return (jax.devices()[0] if self.mesh is None
                else self.mesh.devices.flat[0])

    def block_pool_fits(self, n_blocks: int) -> bool:
        """Whether a block-skip pool of ``n_blocks`` stored blocks fits in
        half of the device's memory (``_POOL_BYTES_PER_BLOCK`` each).  A
        device that reports no limit (the CPU) takes any pool."""
        limit = (self.device().memory_stats() or {}).get("bytes_limit")
        return not limit or n_blocks * _POOL_BYTES_PER_BLOCK <= limit // 2

    def _geometry(self, M: int, N: int) -> tuple[int, int]:
        """Tile sizes of an ``(M, ., N)`` kernel.  A tile that splits its
        dimension into several stripes must be a multiple of
        ``lcm(block, 8)``: the in-place canvas addresses slots in blocks
        and 8-lane groups (``dispatch.canvas_slots``)."""
        tm, tn = self.tile_m, self.tile_n
        if tm is None or tn is None:
            ctm, ctn = choose_tile(M, N)
            tm = tm or ctm
            tn = tn or ctn
        tm, tn = min(tm, M), min(tn, N)
        align = math.lcm(self.block, 8)
        for name, t, dim in (("tile_m", tm, M), ("tile_n", tn, N)):
            if t < dim and t % align:
                raise ValueError(
                    f"{name}={t} splits a dimension of {dim} into several "
                    f"stripes and is not a multiple of lcm(block, 8) = "
                    f"{align}")
        return tm, tn

    def plan(self, x, y, name: str = "kernel") -> KernelPlan:
        """Preprocessing phase: densities → task grid → Analyzer → simulated
        schedule.  Cached on the sparsity structure for ``SparseCOO`` x."""
        if self.faults is not None:
            self.faults.probe("plan", detail=name)
        y = jnp.asarray(y)
        if isinstance(x, SparseCOO):
            M, K = x.shape
        else:
            x = jnp.asarray(x)
            M, K = x.shape
        N = y.shape[1]
        if y.shape[0] != K:
            raise ValueError(
                f"engine.matmul inner-dim mismatch: x is ({M}, {K}), "
                f"y is {tuple(y.shape)}")
        tm, tn = self._geometry(M, N)

        hw = self.runtime_hw()
        struct_key = None
        plan_key = None
        if isinstance(x, SparseCOO):
            struct_key = (coo_fingerprint(x), tm, self.eps)
            # keyed on the EFFECTIVE model's name: a calibrated name encodes
            # (base, backend, block, dtype), so plans decided under the
            # static guesses never shadow calibrated ones or vice versa
            plan_key = (struct_key, K, N, tn, self.mode, self.strategy,
                        hw.name)
            if self.mesh is not None:
                # mesh geometry is part of a placed plan's identity; classic
                # engines keep the historical key shape so their cached plans
                # are untouched by the sharding layer.  Heterogeneous device
                # models shift the band DP, so their names join the key.
                mesh_key = ("mesh", self.n_devices)
                if self.per_device_models is not None:
                    mesh_key += tuple(m.name for m in self.per_device_models)
                plan_key = plan_key + (mesh_key,)
            cached = self.cache.get_plan(plan_key)
            if cached is not None:
                if self.drift_threshold is None:
                    self.last_plan = cached
                    return cached
                # revalidate the first-call Y-density assumption with a
                # cheap row-sampled sketch; replan on drift (stale STQ/DTQ
                # assignment hazard — Dynasparse's re-decide-on-drift)
                sk = sparsity.sketch_col_density(
                    y, tn, max_rows=self.sketch_rows, eps=self.eps)
                drift = sparsity.density_drift(sk, cached.col_density)
                if drift <= self.drift_threshold:
                    self.last_plan = cached
                    return cached
                # a replanned hit amortized nothing: count it as a miss so
                # hit_rate stays an honest effectiveness signal under drift
                self.cache.stats.plan_hits -= 1
                self.cache.stats.plan_misses += 1
                self.cache.stats.replans += 1

        # (1) dynamic density measurement
        if isinstance(x, SparseCOO):
            row_d = self.cache.row_density(
                struct_key,
                lambda: x.row_stripe_density(tm, eps=self.eps))
        else:
            row_d = np.asarray(
                sparsity.stripe_density(x, tm, axis=0, eps=self.eps))
        col_d = np.asarray(
            sparsity.stripe_density(y, tn, axis=1, eps=self.eps))

        # (2) task grid
        part = make_tasks(name, M, K, N, row_d, col_d, tm, tn)

        # (3) analyzer — on the effective (possibly calibrated) model; mesh
        # engines additionally place contiguous stripe bands onto devices
        placement = None
        if self.mesh is not None:
            hws = (list(self.per_device_models)
                   if self.per_device_models is not None
                   else [hw] * self.n_devices)
            stq, dtq, placement = _analyzer.analyze_sharded(
                part, hws, strategy=self.strategy, mode=self.mode)
            rep = _scheduler.simulate_sharded(stq, dtq, placement, hws)
        else:
            if self.mode == "dynamic":
                stq, dtq = _analyzer.analyze_kernel(part, hw, self.strategy)
            elif self.mode == "sparse_only":
                stq, dtq = _analyzer.force_queue(part, hw, "STQ")
            else:
                stq, dtq = _analyzer.force_queue(part, hw, "DTQ")
            # (4) scheduler simulation → hardware-time estimate
            rep = _scheduler.simulate(stq, dtq, hw)
        plan = KernelPlan(part=part, stq=stq, dtq=dtq, report=rep,
                          row_density=np.asarray(row_d),
                          col_density=np.asarray(col_d),
                          struct_key=struct_key, placement=placement)
        if plan_key is not None:
            self.cache.put_plan(plan_key, plan)
        self.last_plan = plan
        return plan

    def _packed_structure(
            self, plan: KernelPlan,
            x: SparseCOO) -> tuple[tuple, StructureEntry]:
        """Packed BlockCSR row-stripes, cached per structure (one packing
        serves every kernel width and every request).  Stripes are packed
        straight from the COO triplets — no dense intermediate — so packing
        stays O(nnz + blocks) beyond toy scale."""
        tm = plan.part.tile_m
        nrt = plan.part.n_row_tiles
        K = x.shape[1]

        def _build() -> StructureEntry:
            if self.faults is not None:
                self.faults.probe("pack", detail=f"stripes:{nrt}")
            rows = np.asarray(x.rows)
            cols = np.asarray(x.cols)
            vals = np.asarray(x.vals)
            order = np.argsort(rows, kind="stable")
            rows, cols, vals = rows[order], cols[order], vals[order]
            bounds = np.searchsorted(rows, np.arange(nrt + 1) * tm)
            stripes = {}
            for i in range(nrt):
                lo, hi = bounds[i], bounds[i + 1]
                stripes[i] = pack_blockcsr_coo(
                    (plan.part.row_extent(i), K),
                    rows[lo:hi] - i * tm, cols[lo:hi], vals[lo:hi],
                    self.block, eps=self.eps)
            return StructureEntry(stripes=stripes)

        key = plan.struct_key + (self.block,)
        return key, self.cache.structure(key, _build)

    def _ensure_dense(self, key: tuple, entry: StructureEntry,
                      x: SparseCOO) -> jnp.ndarray:
        """Materialize the densified operand on first need (a plan routed
        tasks of this operand to the dense engine) and re-account its bytes;
        repeated requests then skip the host->device upload."""
        if entry.dense is None:
            entry.dense = jnp.asarray(x.todense())
            self.cache.recharge(PlanCache._STRUCT, key)
        return entry.dense

    def dispatch_for(self, plan: KernelPlan,
                     x: SparseCOO) -> "_dispatch.CompiledDispatch":
        """The plan's :class:`CompiledDispatch` for a sparse ``x`` (cached;
        lowered on first need).  eps-thresholded SpMM plans compile too —
        the executor masks sub-eps Y blocks inside the traced program, so
        the SpMM stripe walk stays Y-structure-independent
        (``repro.core.dispatch``)."""
        _, entry = self._packed_structure(plan, x)
        digest = _dispatch.plan_digest(plan, self.block)
        return self.cache.dispatch(
            (plan.struct_key, digest),
            lambda: _dispatch.build_dispatch(
                plan.part, plan.stq, plan.dtq, entry.stripes,
                block=self.block, eps=self.eps, fingerprint=digest,
                faults=self.faults))

    def sharded_dispatch_for(
            self, plan: KernelPlan,
            x: SparseCOO) -> "_shard_exec.ShardedDispatch":
        """The placed plan's :class:`~repro.core.shard_exec.ShardedDispatch`
        for a sparse ``x`` on a mesh engine (cached; lowered on first
        need)."""
        _, entry = self._packed_structure(plan, x)
        digest = _dispatch.plan_digest(plan, self.block)
        return self.cache.sharded_dispatch(
            (plan.struct_key, digest, self.n_devices, self.operand_sharding),
            lambda: _shard_exec.build_sharded_dispatch(
                plan.part, plan.stq, plan.dtq, entry.stripes, plan.placement,
                block=self.block, eps=self.eps, fingerprint=digest,
                operand_sharding=self.operand_sharding,
                faults=self.faults, mesh=self.mesh))

    def activation_dispatch_for(
            self, plan: KernelPlan, x, *, capacity=None,
            slack: float = ACTIVATION_SLACK,
            per_stripe: bool = True) -> "_dispatch.ActivationDispatch | None":
        """The plan's :class:`ActivationDispatch` — the capacity-padded
        block-skip route for a dense (activation-side) X — or ``None`` when
        the kernel should stay dense: sparse X (that is
        :meth:`dispatch_for`'s job), plans whose Analyzer routed every task
        to the dense engine (dense wins — a plain GEMM is the whole
        kernel), or a budget whose pool does not fit the device
        (:meth:`block_pool_fits`).

        ``capacity`` fixes the stored-block budget (an int for a uniform
        budget, or a per-stripe vector); by default it is measured from
        ``x`` (the warmup activation) with ``slack`` headroom —
        ``per_stripe=True`` sizes each stripe from ITS OWN warmup need
        (``dispatch.activation_budgets``), cutting padded-slot waste on
        skewed activations; ``per_stripe=False`` keeps the uniform
        max-need budget.  Descriptors are content-INDEPENDENT — cached on
        the plan digest (geometry + ordered assignment) and the budget, so
        every activation kernel with the same shape and task split shares
        one lowering and one trace."""
        if isinstance(x, SparseCOO) or not plan.stq:
            return None
        if capacity is None:
            measure = (_dispatch.activation_budgets if per_stripe
                       else _dispatch.activation_capacity)
            capacity = measure(x, plan.part, self.block, eps=self.eps,
                               slack=slack)
        cap_key = (tuple(int(c) for c in np.asarray(capacity).ravel())
                   if np.ndim(capacity) else int(capacity))
        slots = (sum(cap_key) if isinstance(cap_key, tuple)
                 else cap_key * plan.part.n_row_tiles)
        if not self.block_pool_fits(slots):
            return None
        digest = _dispatch.plan_digest(plan, self.block)
        return self.cache.activation_dispatch(
            (digest, cap_key, self.eps),
            lambda: _dispatch.build_activation_dispatch(
                plan.part, plan.stq, plan.dtq, block=self.block,
                capacity=capacity, eps=self.eps, fingerprint=digest,
                faults=self.faults))

    def route(self, plan: KernelPlan, x, *, activation_skip: bool = True,
              slack: float = ACTIVATION_SLACK,
              per_stripe: bool = True) -> tuple[str, object]:
        """The route a literal engine runs ``plan``'s kernel through, as
        ``(kind, dispatch)`` — one of the compiled program's four record
        kinds (``models.gnn.compile_model``), which the eager pass runs
        too:

        - ``"shard"``: sparse ``x`` on a mesh engine
          (:meth:`sharded_dispatch_for`);
        - ``"sparse"``: sparse ``x`` (:meth:`dispatch_for`);
        - ``"act"``: dense ``x`` whose plan has sparse-queue tasks and whose
          pool fits (:meth:`activation_dispatch_for`; ``slack`` and
          ``per_stripe`` size its budget), unless ``activation_skip`` is
          off;
        - ``"gemm"``: anything else — one dense GEMM, dispatch ``None``."""
        if isinstance(x, SparseCOO):
            if self.mesh is not None:
                return "shard", self.sharded_dispatch_for(plan, x)
            return "sparse", self.dispatch_for(plan, x)
        ad = (self.activation_dispatch_for(plan, x, slack=slack,
                                           per_stripe=per_stripe)
              if activation_skip else None)
        return ("gemm", None) if ad is None else ("act", ad)

    def dense_x(self, plan: KernelPlan, x: SparseCOO, d) -> "jnp.ndarray | None":
        """The densified sparse ``x`` a (sharded) dispatch's dense-queue
        gather reads, or ``None`` when it has no dense-queue tasks."""
        if not d.needs_x:
            return None
        key, entry = self._packed_structure(plan, x)
        return self._ensure_dense(key, entry, x)

    def execute(self, plan: KernelPlan, x, y) -> jnp.ndarray:
        """Functional result of a planned kernel (no re-analysis).

        A literal engine runs the kernel through :meth:`route` — the same
        route, descriptors and kernels the whole-model compiled program
        records for it, each as one jitted call; the route taken is kept
        in ``last_route``.  A non-literal engine computes the product with
        plain ``jax.numpy``."""
        if self.faults is not None:
            self.faults.probe("execute", detail=plan.part.name)
        y = jnp.asarray(y)
        if not self.literal:
            if isinstance(x, SparseCOO):
                return prim.spdmm_exec(x, y)
            return prim.gemm_exec(jnp.asarray(x), y)
        interpret = (_ops.default_interpret()
                     if self.interpret is None else self.interpret)
        kind, d = self.last_route = self.route(plan, x)
        if kind == "shard":
            return _shard_exec.execute_sharded(
                d, self.dense_x(plan, x, d), y, mesh=self.mesh,
                interpret=interpret, stats=self.cache.stats,
                faults=self.faults)
        if self.mesh is not None:
            # a kernel the mesh does not shard runs on the mesh's first
            # device: a Mosaic kernel cannot be partitioned automatically,
            # so operands a sharded kernel left spread out are gathered
            home = self.device()
            y = jax.device_put(y, home)
            x = jax.device_put(jnp.asarray(x), home)
        if kind == "sparse":
            return _dispatch.execute_dispatch(
                d, self.dense_x(plan, x, d), y, interpret=interpret,
                stats=self.cache.stats)
        if kind == "act":
            z, _ = _dispatch.execute_activation(
                d, x, y, interpret=interpret, stats=self.cache.stats)
            return z
        return _ops.gemm(jnp.asarray(x), y, interpret=interpret,
                         out_dtype=jnp.float32)

    # ------------------------------------------------------------------
    def matmul(self, x, y, name: str = "kernel"):
        """Z = X · Y through the runtime system.  ``x`` may be ``SparseCOO``
        (graph adjacency) or a dense array; ``y`` is dense."""
        y = jnp.asarray(y)
        plan = self.plan(x, y, name=name)
        rep = plan.report
        self.report.kernels.append((name, rep))
        self.report.meta.append({
            "name": name,
            "M": plan.part.M, "K": plan.part.K, "N": plan.part.N,
            "x_is_adj": isinstance(x, SparseCOO) and x.tag == "adjacency",
            "alpha_x": float(np.mean(plan.row_density)),
            "alpha_y": float(np.mean(plan.col_density)),
        })
        z = self.execute(plan, x, y)
        return z, rep
