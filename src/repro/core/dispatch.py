"""Compiled dispatch — plan-time lowering of a plan into an instruction stream.

The paper's runtime does its sparsity analysis and kernel mapping ONCE and then
streams work to the PL/AIE engines with near-zero per-kernel overhead (§III,
Alg. 4); GraphAGILE goes further and compiles the whole layer sequence into a
static instruction stream ahead of execution.  This module is that final step
for the TPU runtime: a planned kernel is lowered into a
:class:`CompiledDispatch` — the sorted fused-kernel descriptor arrays (SpDMM
and SpMM entry lists, batched-GEMM tile coordinates), the pooled
BlockCSR block payloads, and the padded-canvas geometry — built once with
vectorized numpy (no per-nonzero-block Python loops) and kept device-resident
in the :class:`~repro.core.plancache.PlanCache`.

Steady-state execution then goes through :func:`execute_dispatch`: ONE jitted
end-to-end program per (geometry, operand signature) that chains
pad → gemm_batch_scatter → spdmm_fused (SpDMM section) → spdmm_fused (SpMM
section) → slice with the descriptors as device arrays, so a plan-cache hit
costs O(1) dict lookups on the host instead of O(nnz blocks) of descriptor
rebuilding.

Semantics of the sections:

- The dense queue is ONE batched GEMM whose tiles scatter into the padded
  canvas; the SpDMM section is ONE fused entry list over the pooled stored
  blocks of A.
- SpMM descriptors must be Y-structure-independent to be cacheable, so the
  compiled SpMM section does not intersect Y's structure at all: it is a
  STRIPE WALK in the SpDMM entry format, one grid step per (stored A block,
  task column stripe) — each step multiplies the A block into its Y
  block-row across the whole ``SN``-wide stripe.  These are the
  multiply-adds of pairing every stored A block with every 8x8 Y block of
  the stripe (zero Y blocks contribute ``x + (±0) == x``, bitwise, to an
  accumulator initialized to +0), in the same A-block order per output
  block, but in ``nnzb`` grid steps per task where the 8-wide pairing took
  ``nnzb * SN / B``.  One (B, B)@(B, SN) dot rounds like ``SN / B`` dots of
  width B only up to about one ulp, so the result equals the same tasks run
  as SpDMM bit for bit, and a structure-intersecting SpMM within float32
  rounding.  With ``eps != 0`` an eps-thresholded pack *drops*
  small-but-nonzero Y blocks the stripe walk would read, so the executor
  zeroes the Y blocks whose magnitudes are all ``<= eps`` on device before
  the SpMM section (the SpDMM section reads the unmasked operand, hence its
  own launch) — eps-thresholded SpMM plans compile like any other.
- A sparse section whose tasks cover every column tile of each row stripe
  they touch WALKS WHOLE ROWS (:func:`walks_whole_rows`): one grid step per
  stored block over the full ``nct * SN``-wide padded output row instead of
  one per (stored block, column tile).  Each output column still sums the
  same products in the same A-block order; the choice is static in the
  geometry (``sp_rows`` / ``mm_rows``) and the halo-sharded path makes the
  same one, so the two stay bit-identical.

Activation-side kernels (dense X — the intermediate feature matrices) get the
same treatment through :class:`ActivationDispatch`: the descriptor arrays are
**capacity-parameterized** — they enumerate ``capacity`` stored-block SLOTS
per row-stripe instead of concrete stored blocks — and the slots are filled
at run time by the device-resident packer
(:func:`repro.kernels.ops.pack_activation_stripes`), whose per-slot metadata
(block-row, block-col, first-visit) rides into the fused kernels as runtime
scalar-prefetch operands.  One trace therefore serves ANY activation sparsity
within the stored-block budget; a batch that overflows the budget takes a
dense-GEMM fallback INSIDE the same program (``lax.cond``), never a retrace.
This is what recovers the paper's dynamic intermediate-data block-skip in the
compiled whole-model steady state (ROADMAP item (a); GraphAGILE's fixed-
budget overlay scheduling is the shape-stability precedent).
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import halo as _halo
from repro.kernels import ops
from repro.kernels.formats import BlockCSR, block_nonzero_mask


def canvas_slots(part, block: int) -> tuple[int, int]:
    """Slot sizes ``(SM, SN)`` of the padded in-place canvas.  Interior tile
    boundaries must be lcm(block, 8)-aligned for the in-place index maps, so
    only a single stripe's slot may pad past its tile; any other geometry
    raises ``ValueError`` (the engine never plans one)."""
    align = math.lcm(block, 8)
    tm, tn = part.tile_m, part.tile_n
    SM = tm if tm % align == 0 else -(-tm // align) * align
    SN = tn if tn % align == 0 else -(-tn // align) * align
    if (part.n_row_tiles > 1 and SM != tm) or (part.n_col_tiles > 1 and SN != tn):
        raise ValueError(
            f"tiles ({tm}, {tn}) split the kernel into stripes whose "
            f"boundaries are not multiples of lcm(block, 8) = {align}")
    return SM, SN


@dataclasses.dataclass(frozen=True)
class DispatchGeometry:
    """Hashable static shape of a compiled dispatch — the jit cache key's
    static half (two dispatches with equal geometry share one trace)."""
    M: int
    K: int
    N: int
    tm: int
    tn: int
    SM: int
    SN: int
    B: int
    nrt: int
    nct: int
    has_gemm: bool
    has_spdmm: bool
    has_spmm: bool
    # nonzero tolerance applied to the dense operand's blocks inside the
    # traced SpMM (sub-eps Y blocks are zeroed on device — see module doc)
    eps: float = 0.0
    # the SpDMM / SpMM section walks whole padded output rows
    # (:func:`walks_whole_rows`) instead of one column stripe at a time
    sp_rows: bool = False
    mm_rows: bool = False

    @property
    def m_pad(self) -> int:
        return self.nrt * self.SM

    @property
    def n_pad(self) -> int:
        return self.nct * self.SN

    @property
    def ncb(self) -> int:
        return -(-self.K // self.B)


@dataclasses.dataclass
class CompiledDispatch:
    """Device-resident instruction stream of one planned kernel.

    ``arrays`` holds the descriptor index arrays (int32) and the pooled
    stored-block payloads (float) — everything :func:`execute_dispatch`
    streams to the fused kernels.  ``fingerprint`` content-addresses the
    (structure, task assignment, geometry) this dispatch lowers, so a
    density-drift replan that lands on the same assignment transparently
    reuses it while a changed assignment misses to a fresh build.
    """
    geom: DispatchGeometry
    arrays: dict[str, jax.Array]
    fingerprint: str

    @property
    def needs_x(self) -> bool:
        """True when the dense-queue gather needs the densified X operand."""
        return self.geom.has_gemm

    @property
    def n_entries(self) -> int:
        """Grid steps of the SpDMM section (one per entry)."""
        a = self.arrays.get("sp_a_ids")
        return 0 if a is None else int(a.shape[0])

    @property
    def n_spmm_steps(self) -> int:
        """Grid steps of the SpMM section's stripe walk."""
        a = self.arrays.get("mm_a_ids")
        return 0 if a is None else int(a.shape[0])

    @property
    def sparse_steps(self) -> int:
        """Grid steps of both sparse sections."""
        return self.n_entries + self.n_spmm_steps

    @property
    def row_walk_sections(self) -> int:
        """Sparse sections lowered as whole-row walks."""
        return int(self.geom.sp_rows) + int(self.geom.mm_rows)


def plan_digest(plan, block: int) -> str:
    """Content digest of everything a dispatch is lowered from: operand
    structure key, kernel geometry, and the ORDERED task assignment (entry
    sequencing follows queue order, so order is part of the identity).

    Memoized on the plan instance — the assignment is immutable once
    planned, and hashing O(tasks) per request would reintroduce exactly the
    per-request host work the compiled path exists to remove (a replan
    builds a fresh ``KernelPlan``, so staleness is impossible)."""
    memo = getattr(plan, "_dispatch_digest", None)
    if memo is not None and memo[0] == block:
        return memo[1]
    h = hashlib.blake2b(digest_size=16)
    part = plan.part
    h.update(repr((plan.struct_key, part.M, part.K, part.N,
                   part.tile_m, part.tile_n, block)).encode())
    h.update(repr([(t.i, t.j, t.primitive) for t in plan.stq]).encode())
    h.update(repr([(t.i, t.j) for t in plan.dtq]).encode())
    placement = getattr(plan, "placement", None)
    if placement is not None:
        # Mesh geometry is part of a sharded dispatch's identity; unsharded
        # plans hash exactly as before so existing digests stay stable.
        h.update(repr(("mesh", placement.n_devices,
                       placement.band_starts)).encode())
        # Ownership geometry of the owned+halo operand layout: derived
        # deterministically from (part, placement, block), hashed so the
        # digest names the support geometry a halo dispatch is lowered for.
        h.update(repr(("own", _halo.ownership_starts(
            part.M, part.K, part.tile_m, placement.band_starts, block))
        ).encode())
    digest = h.hexdigest()
    try:
        plan._dispatch_digest = (block, digest)
    except Exception:   # frozen/slotted future variants: just recompute
        pass
    return digest


def _stripe_pool(tasks, stripes) -> tuple[dict[int, int], jax.Array]:
    """Concatenate the stored blocks of every row-stripe a task list touches
    into one device pool; returns (stripe index -> pool offset, pool)."""
    offsets: dict[int, int] = {}
    pool = []
    off = 0
    for i in sorted({t.i for t in tasks}):
        offsets[i] = off
        pool.append(stripes[i].blocks[: stripes[i].nnzb])
        off += stripes[i].nnzb
    return offsets, jnp.concatenate(pool, axis=0)


# the per-entry descriptor arrays of a fused sparse section, in the order
# :func:`spdmm_entry_arrays` returns them and the fused kernels take them
ENTRY_FIELDS = ("a_ids", "y_rows", "out_rows", "out_cols", "first")


def walks_whole_rows(tasks, nct: int, SN: int, B: int) -> bool:
    """True when a sparse section's tasks can run as a whole-row walk: one
    grid step per stored block over the full ``nct * SN``-wide padded output
    row rather than one per (stored block, column tile).  It takes more than
    one column tile, at least one task, tasks that cover every column tile
    of each row stripe they touch (so no other section writes those rows),
    and a row narrow enough for its blocks to sit in VMEM
    (``ops.MAX_ROW_BLOCK_BYTES``)."""
    if nct < 2 or B * nct * SN * 4 > ops.MAX_ROW_BLOCK_BYTES:
        return False
    cover: dict[int, set[int]] = {}
    for t in tasks:
        cover.setdefault(t.i, set()).add(t.j)
    return bool(cover) and all(len(js) == nct for js in cover.values())


def spdmm_entry_arrays(tasks, stripes: dict[int, "BlockCSR"],
                       offsets: dict[int, int], R: int, *,
                       whole_rows: bool = False):
    """Vectorized fused-SpDMM entry list over all tasks of one kernel.

    Returns ``(a_ids, y_rows, out_rows, out_cols, first)`` sorted by output
    block with queue order as the tiebreak — element-for-element identical to
    the per-block Python loop it replaces (the stripes' own ``first`` flags
    are carried through the sort: within one output block's run the entries
    are one stripe's one block-row in stored order, whose first stored block
    is flagged 1).  With ``whole_rows`` (:func:`walks_whole_rows`) each
    stored block of a touched stripe gets ONE entry with ``out_cols`` 0,
    for a launch whose ``bn`` spans the whole padded row.
    """
    units = ([(i, 0) for i in dict.fromkeys(t.i for t in tasks)]
             if whole_rows else [(t.i, t.j) for t in tasks])
    out_rows, out_cols, a_ids, y_rows, firsts = [], [], [], [], []
    for i, j in units:
        s = stripes[i]
        nb = s.nnzb
        rid = np.asarray(s.row_ids)[:nb]
        out_rows.append(i * R + rid.astype(np.int64))
        out_cols.append(np.full(nb, j, dtype=np.int64))
        a_ids.append(offsets[i] + np.arange(nb, dtype=np.int64))
        y_rows.append(np.asarray(s.col_ids)[:nb].astype(np.int64))
        firsts.append(np.asarray(s.first)[:nb].astype(np.int64))
    out_rows = np.concatenate(out_rows)
    out_cols = np.concatenate(out_cols)
    a_ids = np.concatenate(a_ids)
    y_rows = np.concatenate(y_rows)
    firsts = np.concatenate(firsts)
    seq = np.arange(len(out_rows))
    order = np.lexsort((seq, out_cols, out_rows))
    return (a_ids[order].astype(np.int32), y_rows[order].astype(np.int32),
            out_rows[order].astype(np.int32), out_cols[order].astype(np.int32),
            firsts[order].astype(np.int32))


def build_dispatch(part, stq, dtq, stripes: dict[int, "BlockCSR"],
                   *, block: int, eps: float = 0.0,
                   fingerprint: str = "",
                   faults: object = None) -> CompiledDispatch:
    """Lower a planned kernel into a :class:`CompiledDispatch`.

    O(nnz blocks) of VECTORIZED numpy + one device upload, paid once per
    (structure, assignment, geometry); a partition whose canvas cannot take
    the in-place index maps raises ``ValueError`` (:func:`canvas_slots`).
    ``faults`` is the optional fault injector probed at the ``lower`` site
    — descriptor lowering is an instrumented degradation path.
    """
    if faults is not None:
        faults.probe("lower", detail=f"dispatch:{part.name}")
    SM, SN = canvas_slots(part, block)
    B = block
    R = SM // B
    nct = part.n_col_tiles
    sections = {"sp": [t for t in stq if t.primitive != "SpMM"],
                "mm": [t for t in stq if t.primitive == "SpMM"]}
    rows = {p: walks_whole_rows(tasks, nct, SN, B)
            for p, tasks in sections.items()}
    geom = DispatchGeometry(
        M=part.M, K=part.K, N=part.N, tm=part.tile_m, tn=part.tile_n,
        SM=SM, SN=SN, B=B, nrt=part.n_row_tiles, nct=nct,
        has_gemm=bool(dtq),
        has_spdmm=bool(sections["sp"]), has_spmm=bool(sections["mm"]),
        eps=eps, sp_rows=rows["sp"], mm_rows=rows["mm"])
    arrays: dict[str, jax.Array] = {}

    if dtq:
        arrays["gemm_rows"] = jnp.asarray(
            np.array([t.i for t in dtq], dtype=np.int32))
        arrays["gemm_cols"] = jnp.asarray(
            np.array([t.j for t in dtq], dtype=np.int32))

    # both sparse sections in the SpDMM entry format: the SpMM section is
    # the stripe walk (module docstring), a launch of its own
    for prefix, tasks in sections.items():
        if not tasks:
            continue
        offsets, pool = _stripe_pool(tasks, stripes)
        arrays[f"{prefix}_pool"] = pool
        for name, v in zip(ENTRY_FIELDS, spdmm_entry_arrays(
                tasks, stripes, offsets, R, whole_rows=rows[prefix])):
            arrays[f"{prefix}_{name}"] = jnp.asarray(v)

    return CompiledDispatch(geom=geom, arrays=arrays, fingerprint=fingerprint)


# --------------------------------------------------------------- execution
def _stripe_padded_y(geom, y):
    """Dense operand laid out with each col-stripe padded to ``SN`` columns
    and K padded to block multiples — the fused kernels' Y layout.  Works
    for both geometry kinds (duck-typed on the shared fields)."""
    B = geom.B
    ncb = geom.ncb
    y_pad = jnp.pad(y, ((0, ncb * B - geom.K),
                        (0, geom.nct * geom.tn - geom.N)))
    return jnp.pad(y_pad.reshape(ncb * B, geom.nct, geom.tn),
                   ((0, 0), (0, 0), (0, geom.SN - geom.tn))
                   ).reshape(ncb * B, geom.nct * geom.SN)


def _eps_masked_y(geom, y_f):
    """Stripe-padded dense operand with the eps mask applied on device:
    blocks whose magnitudes are all ``<= eps`` are zeroed, so the
    structure-independent SpMM lowerings contribute exact bitwise no-ops
    for exactly the blocks an eps-thresholded eager pack would have
    dropped.  The operand itself when ``eps == 0``."""
    if geom.eps == 0.0:
        return y_f
    B = geom.B
    yb = y_f.reshape(y_f.shape[0] // B, B, y_f.shape[1] // B, B)
    keep = block_nonzero_mask(yb, geom.eps, axis=(1, 3), xp=jnp)
    return jnp.where(keep[:, None, :, None], yb,
                     jnp.zeros((), y_f.dtype)).reshape(y_f.shape)


def _gemm_y_panel(geom, y):
    """Col-stripe-padded GEMM operand panel ``(K, nct, SN)`` from the raw
    dense operand — the layout ``gemm_batch_scatter`` gathers col stripes
    from."""
    y_p = jnp.pad(y, ((0, 0), (0, geom.nct * geom.tn - geom.N))
                  ).reshape(geom.K, geom.nct, geom.tn)
    if geom.SN != geom.tn:
        y_p = jnp.pad(y_p, ((0, 0), (0, 0), (0, geom.SN - geom.tn)))
    return y_p


def _gemm_scatter_panel(geom, arrays, x, y_p, z, *, interpret: bool):
    """Dense-queue section on a PRE-BUILT operand panel: gather the tasks'
    row/col stripes and scatter one batched GEMM into the canvas."""
    rows, cols = arrays["gemm_rows"], arrays["gemm_cols"]
    x_p = jnp.pad(x, ((0, geom.m_pad - geom.M), (0, 0)))
    xs = x_p.reshape(geom.nrt, geom.SM, geom.K)[rows]
    ys = jnp.moveaxis(y_p, 1, 0)[cols]
    return ops.gemm_batch_scatter(xs, ys, rows, cols, z, interpret=interpret)


def _gemm_scatter(geom, arrays, x, y, z, *, interpret: bool):
    """Dense-queue section shared by both dispatch kinds (raw-operand
    entry point, kept for the activation path)."""
    return _gemm_scatter_panel(geom, arrays, x, _gemm_y_panel(geom, y), z,
                               interpret=interpret)


def apply_dispatch(geom: DispatchGeometry, arrays, x, y, *, interpret: bool):
    """Traceable end-to-end executor body: pad → batched GEMM scatter →
    fused SpDMM → SpMM stripe walk → slice, on ONE aliased canvas.  ``x`` (the
    densified operand) may be ``None`` when the plan has no dense-queue
    tasks.  Inlines into larger jitted programs (`models.gnn.compile_model`).
    """
    if geom.has_gemm and x is None:
        raise ValueError("compiled dispatch: dense-queue tasks need the "
                         "densified x operand (got x=None)")
    with jax.named_scope("layout"):
        y_f = (_stripe_padded_y(geom, y)
               if (geom.has_spdmm or geom.has_spmm) else None)
        y_p = _gemm_y_panel(geom, y) if geom.has_gemm else None
    return apply_prepared(geom, arrays, x, y_f, y_p, interpret=interpret)


def apply_prepared(geom: DispatchGeometry, arrays, x, y_f, y_p,
                   *, interpret: bool):
    """Executor body on PRE-LAID-OUT dense operands: ``y_f`` is the
    stripe-padded operand matrix (ANY block-row count — the halo-sharded
    path passes each shard's LOCAL owned+halo buffer, whose slots the
    descriptors were lowered against), ``y_p`` the GEMM panel (required
    when ``geom.has_gemm``).  The fused kernels index Y only through the
    descriptor block-row ids, so the operand's leading extent is free."""
    B, SM, SN = geom.B, geom.SM, geom.SN
    M_pad, N_pad = geom.m_pad, geom.n_pad
    z = jnp.zeros((M_pad, N_pad), dtype=jnp.float32)

    if geom.has_gemm:
        z = _gemm_scatter_panel(geom, arrays, x, y_p, z, interpret=interpret)

    # a whole-row section runs as one stripe spanning the padded row
    if geom.has_spdmm:
        z = ops.spdmm_fused(
            arrays["sp_pool"], y_f, arrays["sp_a_ids"], arrays["sp_y_rows"],
            arrays["sp_out_rows"], arrays["sp_out_cols"], arrays["sp_first"],
            block_size=B, bn=N_pad if geom.sp_rows else SN, m_pad=M_pad,
            interpret=interpret, z=z)

    if geom.has_spmm:
        z = ops.spdmm_fused(
            arrays["mm_pool"], _eps_masked_y(geom, y_f), arrays["mm_a_ids"],
            arrays["mm_y_rows"], arrays["mm_out_rows"], arrays["mm_out_cols"],
            arrays["mm_first"], block_size=B,
            bn=N_pad if geom.mm_rows else SN, m_pad=M_pad,
            interpret=interpret, z=z, name="spmm_stripe")

    return z[:geom.M, :geom.N]


@functools.partial(jax.jit, static_argnames=("geom", "interpret"))
def _run_dispatch(geom, arrays, x, y, *, interpret):
    return apply_dispatch(geom, arrays, x, y, interpret=interpret)


# Trace-cache observability: jax.jit caches per (geometry, operand signature);
# this mirror of that key set lets engines report honest trace hit counts.
_TRACE_SEEN: set = set()
_TRACE_LOCK = threading.Lock()


def _signature(geom, arrays, x, y, interpret):
    arr_sig = tuple(sorted((k, v.shape, str(v.dtype))
                           for k, v in arrays.items()))
    x_sig = None if x is None else (tuple(x.shape), str(x.dtype))
    return (geom, arr_sig, x_sig, tuple(y.shape), str(y.dtype), interpret)


def reset_trace_registry() -> None:
    """Forget which executor signatures were seen (tests/benchmarks).  Note
    jax's own jit cache is NOT cleared — after a reset the first call per
    signature is counted as a build again even though jax may reuse its
    trace; pair with ``jax.clear_caches()`` when that distinction matters."""
    with _TRACE_LOCK:
        _TRACE_SEEN.clear()


def execute_dispatch(d: CompiledDispatch, x, y, *, interpret: bool,
                     stats=None) -> jax.Array:
    """Run one compiled kernel: a single jitted call, zero host descriptor
    work.  ``stats`` (a ``CacheStats``) receives trace-cache accounting."""
    y = jnp.asarray(y)
    key = _signature(d.geom, d.arrays, x, y, interpret)
    with _TRACE_LOCK:
        hit = key in _TRACE_SEEN
        _TRACE_SEEN.add(key)
    if stats is not None:
        if hit:
            stats.trace_cache_hits += 1
        else:
            stats.trace_builds += 1
    return _run_dispatch(d.geom, d.arrays, x, y, interpret=interpret)


# ------------------------------------ activation-side capacity block-skip
@dataclasses.dataclass(frozen=True)
class ActivationGeometry(DispatchGeometry):
    """Hashable static shape of a compiled ACTIVATION dispatch.

    Extends :class:`DispatchGeometry` with the stored-block budget per
    row-stripe — because the descriptor arrays enumerate capacity slots,
    not concrete stored blocks: the trace key must distinguish two budgets,
    but NOT two sparsity patterns (that independence is the whole point).
    The budget is either uniform (``cap``, historical layout) or a
    per-stripe vector (``caps`` — skew-aware: each stripe only as many
    slots as its warmup need × slack; stripes live at flat offsets
    ``cumsum(caps)``).  Dataclass equality is class-aware, so an activation
    geometry never collides with an adjacency one in the jit/trace
    registries.
    """
    cap: int = 0
    # per-stripe budgets; empty tuple = uniform ``cap`` for every stripe
    caps: tuple = ()

    @property
    def R(self) -> int:
        return self.SM // self.B

    @property
    def C(self) -> int:
        return self.SN // self.B

    @property
    def cap_vec(self) -> np.ndarray:
        """Per-stripe budget vector (length ``nrt``), whichever form the
        geometry stores."""
        if self.caps:
            return np.asarray(self.caps, dtype=np.int64)
        return np.full(self.nrt, self.cap, dtype=np.int64)

    @property
    def slot_offsets(self) -> np.ndarray:
        """Flat slot offset of each stripe (length ``nrt + 1``)."""
        return np.concatenate(
            [np.zeros(1, np.int64), np.cumsum(self.cap_vec)])

    @property
    def total_slots(self) -> int:
        return int(self.cap_vec.sum())


@dataclasses.dataclass
class ActivationDispatch:
    """Capacity-parameterized instruction stream of one activation-side
    (dense X) kernel.  ``arrays`` holds ONLY static int32 descriptor arrays
    — slot ids, output col-stripes, base rows — valid for every input; the
    data-dependent half (block payloads, per-slot block-row/col/first) is
    produced at run time by the device packer and joined to these
    descriptors inside the traced program."""
    geom: ActivationGeometry
    arrays: dict[str, jax.Array]
    fingerprint: str

    @property
    def n_entries(self) -> int:
        a = self.arrays.get("asp_a_ids")
        return 0 if a is None else int(a.shape[0])

    @property
    def n_triples(self) -> int:
        a = self.arrays.get("amm_a_ids")
        return 0 if a is None else int(a.shape[0])


def activation_capacity(x, part, block: int, *, eps: float = 0.0,
                        slack: float = 1.5) -> int:
    """Stored-block budget per row-stripe from a warmup activation.

    Counts, per canvas row-stripe, the slots the device packer will need
    (stored blocks plus one filler per empty block-row, canvas padding rows
    included) and budgets ``max * slack`` so later batches whose sparsity
    wiggles within the drift threshold still fit without a retrace.
    """
    needs = _stripe_needs(x, part, block, eps=eps)
    R, C = _canvas_rc(part, block)
    return min(R * C, max(1, math.ceil(int(needs.max()) * slack)))


def _canvas_rc(part, block: int) -> tuple[int, int]:
    SM, _ = canvas_slots(part, block)
    return SM // block, -(-part.K // block)


def _stripe_needs(x, part, block: int, *, eps: float = 0.0):
    """Per-stripe slot needs of a warmup activation (stored blocks plus one
    filler per empty block-row, canvas padding rows included) — the shared
    counting core of the uniform and per-stripe budget sizers."""
    SM, _ = canvas_slots(part, block)
    B = block
    S, R, C = part.n_row_tiles, SM // B, -(-part.K // B)
    x = np.asarray(x)
    xp = np.zeros((S * R * B, C * B), dtype=x.dtype)
    xp[: x.shape[0], : x.shape[1]] = x
    xb = xp.reshape(S, R, B, C, B)
    mask = block_nonzero_mask(xb, eps, axis=(2, 4))
    return np.maximum(mask.sum(axis=2), 1).sum(axis=1)     # (S,)


def activation_budgets(x, part, block: int, *, eps: float = 0.0,
                       slack: float = 1.5):
    """Per-stripe stored-block budget VECTOR from a warmup activation.

    The skew-aware refinement of :func:`activation_capacity`: each stripe
    is budgeted ``its own need × slack`` (clamped to ``[1, R*C]``) instead
    of every stripe paying for the densest one.  On skewed activations this
    cuts padded-slot waste proportionally to the skew, and since drift only
    wiggles a fixed support, warmup needs bound later needs per stripe just
    as they do globally.  Returns an int64 array of length
    ``part.n_row_tiles``.
    """
    needs = _stripe_needs(x, part, block, eps=eps)
    R, C = _canvas_rc(part, block)
    return np.clip(np.ceil(needs * slack).astype(np.int64), 1, R * C)


def build_activation_dispatch(part, stq, dtq, *, block: int, capacity,
                              eps: float = 0.0, fingerprint: str = "",
                              faults: object = None
                              ) -> ActivationDispatch:
    """Lower an activation-side plan into capacity-slot descriptor arrays.

    ``capacity`` is a uniform int budget or a per-stripe vector (see
    :func:`activation_budgets`); descriptors address slots at the stripe's
    flat offset, so the uniform case keeps its historical
    ``stripe * cap + slot`` layout exactly.  Entry order is (task, slot)
    for SpDMM and (task, y-block-col, slot) for SpMM: within one ordering
    unit the runtime slot metadata is row-major, so every output block is
    still visited in ONE consecutive run (the TPU output-residency
    obligation) for ANY stored pattern — and within a run the real
    contributions arrive in the same (block-row, block-col) order a host
    ``pack_blockcsr`` emits, so sums are bit-identical to its unfused
    kernels.  A canvas the in-place index maps cannot take raises
    ``ValueError`` (:func:`canvas_slots`).
    """
    if faults is not None:
        faults.probe("pack", detail=f"act:{part.name}")
    SM, SN = canvas_slots(part, block)
    B = block
    R, C = SM // B, SN // B
    cap_arr = np.asarray(capacity, dtype=np.int64)
    uniform = cap_arr.ndim == 0
    if uniform:
        cap_arr = np.full(part.n_row_tiles, int(cap_arr), dtype=np.int64)
    assert cap_arr.shape == (part.n_row_tiles,), (cap_arr.shape, part)
    offs = np.concatenate([np.zeros(1, np.int64), np.cumsum(cap_arr)])
    geom = ActivationGeometry(
        M=part.M, K=part.K, N=part.N, tm=part.tile_m, tn=part.tile_n,
        SM=SM, SN=SN, B=B, nrt=part.n_row_tiles, nct=part.n_col_tiles,
        cap=int(cap_arr[0]) if uniform else 0,
        caps=() if uniform else tuple(int(c) for c in cap_arr),
        eps=eps,
        has_gemm=bool(dtq),
        has_spdmm=any(t.primitive != "SpMM" for t in stq),
        has_spmm=any(t.primitive == "SpMM" for t in stq))
    arrays: dict[str, jax.Array] = {}

    if dtq:
        arrays["gemm_rows"] = jnp.asarray(
            np.array([t.i for t in dtq], dtype=np.int32))
        arrays["gemm_cols"] = jnp.asarray(
            np.array([t.j for t in dtq], dtype=np.int32))

    spdmm_tasks = sorted((t for t in stq if t.primitive != "SpMM"),
                         key=lambda t: (t.i, t.j))
    spmm_tasks = sorted((t for t in stq if t.primitive == "SpMM"),
                        key=lambda t: (t.i, t.j))

    if spdmm_tasks:
        arrays["asp_a_ids"] = jnp.asarray(np.concatenate(
            [offs[t.i] + np.arange(cap_arr[t.i], dtype=np.int64)
             for t in spdmm_tasks]).astype(np.int32))
        arrays["asp_out_cols"] = jnp.asarray(np.concatenate(
            [np.full(cap_arr[t.i], t.j, dtype=np.int64)
             for t in spdmm_tasks]).astype(np.int32))
        arrays["asp_base_rows"] = jnp.asarray(np.concatenate(
            [np.full(cap_arr[t.i], t.i * R, dtype=np.int64)
             for t in spdmm_tasks]).astype(np.int32))

    if spmm_tasks:
        a_ids, y_cols, base_rows = [], [], []
        for t in spmm_tasks:
            nbj = -(-part.col_extent(t.j) // B)
            cap_i = int(cap_arr[t.i])
            a_ids.append(np.tile(
                offs[t.i] + np.arange(cap_i, dtype=np.int64), nbj))
            y_cols.append(np.repeat(t.j * C + np.arange(nbj, dtype=np.int64),
                                    cap_i))
            base_rows.append(np.full(nbj * cap_i, t.i * R, dtype=np.int64))
        arrays["amm_a_ids"] = jnp.asarray(
            np.concatenate(a_ids).astype(np.int32))
        # y block-col == output block-col for every triple of a task
        arrays["amm_y_cols"] = jnp.asarray(
            np.concatenate(y_cols).astype(np.int32))
        arrays["amm_base_rows"] = jnp.asarray(
            np.concatenate(base_rows).astype(np.int32))

    return ActivationDispatch(geom=geom, arrays=arrays,
                              fingerprint=fingerprint)


def apply_activation_dispatch(geom: ActivationGeometry, arrays, x, y, *,
                              interpret: bool):
    """Traceable activation-side executor: device-pack X into capacity
    slots, join the slot metadata to the static descriptors, and drain the
    plan's queues on one canvas — or, when the batch overflows the budget,
    fall back to ONE dense GEMM inside the same program (``lax.cond``:
    same trace, no recompilation, the result is the plain dense route's).

    Returns ``(z, diag)`` where ``diag`` carries the block-skip telemetry
    the serving layer and the benchmark gate consume: ``stored`` (total
    slots filled with real blocks), ``capacity``/``logical`` (budget and
    full block count), and the ``overflow`` flag."""
    B, SM, SN = geom.B, geom.SM, geom.SN
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    with jax.named_scope("pack"):
        (pool, row_m, col_m, first_m, nnzb, real,
         overflow) = ops.pack_activation_stripes(
            x, block=B, n_stripes=geom.nrt, slot_rows=geom.R,
            n_block_cols=geom.ncb,
            capacity=np.asarray(geom.caps) if geom.caps else geom.cap,
            eps=geom.eps)

    @jax.named_scope("dense")
    def _dense():
        return ops.gemm(x, y, interpret=interpret, out_dtype=jnp.float32)

    @jax.named_scope("skip")
    def _skip():
        z = jnp.zeros((geom.m_pad, geom.n_pad), dtype=jnp.float32)
        if geom.has_gemm:
            z = _gemm_scatter(geom, arrays, x, y, z, interpret=interpret)
        if geom.has_spdmm or geom.has_spmm:
            y_f = _stripe_padded_y(geom, y)
        if geom.has_spdmm:
            a_ids = arrays["asp_a_ids"]
            z = ops.spdmm_fused(
                pool, y_f, a_ids, col_m[a_ids],
                arrays["asp_base_rows"] + row_m[a_ids],
                arrays["asp_out_cols"], first_m[a_ids],
                block_size=B, bn=SN, m_pad=geom.m_pad, interpret=interpret,
                z=z)
        if geom.has_spmm:
            y_blocks = ops.blockize(_eps_masked_y(geom, y_f), B)
            a_ids = arrays["amm_a_ids"]
            y_ids = col_m[a_ids] * (geom.nct * geom.C) + arrays["amm_y_cols"]
            z = ops.spmm_fused(
                pool, y_blocks, a_ids, y_ids,
                arrays["amm_base_rows"] + row_m[a_ids],
                arrays["amm_y_cols"], first_m[a_ids],
                block_size=B, m_pad=geom.m_pad, n_pad=geom.n_pad,
                interpret=interpret, z=z)
        return z[:geom.M, :geom.N]

    z = jax.lax.cond(overflow, _dense, _skip)
    # ``stored`` counts REAL blocks (empty-row fillers excluded) and
    # ``logical`` the block positions of the LOGICAL extent (canvas padding
    # rows excluded), so 1 - stored/logical is the honest skip ratio: 0 for
    # a dense activation, ~1 for an all-zero one.
    diag = {
        "stored": jnp.sum(real),
        "capacity": jnp.int32(geom.total_slots),
        "logical": jnp.int32(-(-geom.M // geom.B) * geom.ncb),
        "overflow": overflow,
    }
    return z, diag


@functools.partial(jax.jit, static_argnames=("geom", "interpret"))
def _run_activation(geom, arrays, x, y, *, interpret):
    return apply_activation_dispatch(geom, arrays, x, y, interpret=interpret)


def execute_activation(d: ActivationDispatch, x, y, *, interpret: bool,
                       stats=None):
    """Run one activation-side kernel through the capacity block-skip route:
    a single jitted call whose trace is reused for EVERY input sparsity
    within budget.  Returns ``(z, diag)``; ``stats`` receives the same
    trace-cache accounting as :func:`execute_dispatch`."""
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    key = _signature(d.geom, d.arrays, x, y, interpret)
    with _TRACE_LOCK:
        hit = key in _TRACE_SEEN
        _TRACE_SEEN.add(key)
    if stats is not None:
        if hit:
            stats.trace_cache_hits += 1
        else:
            stats.trace_builds += 1
    return _run_activation(d.geom, d.arrays, x, y, interpret=interpret)
